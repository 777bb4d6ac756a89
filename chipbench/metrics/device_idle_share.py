"""Share of the traced window in which no operation ran on the chip that
holds the KV pool: one minus the union of its ``XLA Ops`` intervals."""


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    return 100.0 * tr["idle_share"]
