"""Highest share of the KV pool's pages mapped at any step boundary of the
window (the virtualizer's ``mapped_pages`` over its ``page_budget``)."""


def read(run):
    mapped = run["registry"].get("kv_mapped")
    if not mapped:
        return None
    return 100.0 * max(mapped) / run["registry"]["page_budget"]
