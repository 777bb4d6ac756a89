"""Prompt-pass time per thousand prompt tokens: the engine registry's
``crosspool_prefill_dispatch_seconds`` over the window, divided by the
true prompt tokens of the requests whose first token came in the window."""


def read(run):
    secs = run["registry"].get("prefill_seconds")
    toks = run["registry"].get("prefill_tokens")
    if not secs or not toks:
        return None
    return 1e3 * secs / (toks / 1e3)
