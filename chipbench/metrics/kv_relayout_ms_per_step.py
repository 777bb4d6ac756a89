"""Device self time under the ``kv_write`` and ``kv_view`` scopes (the
new token's write into the pool and the typing of the pages a request's
table gathers) per traced decode-only step, in ms (``cb.scopes``)."""
from cb.scopes import RELAYOUT, decode_only_scopes, seconds_under


def read(run):
    steps = decode_only_scopes(run)
    if not steps:
        return None
    return 1e3 * sum(seconds_under(sc, *RELAYOUT)
                     for _, sc in steps) / len(steps)
