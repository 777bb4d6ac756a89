"""Bytes the attention stage of the traced decode-only steps must move
(``cb.stage_work``: weights, norms, KV read and written), over the device
self time under the ``attn`` scope in those steps (``cb.scopes``) and the
chip's HBM bandwidth, as a percentage."""
from cb.stage_work import stage_roofline


def read(run):
    return stage_roofline(run, "attn")
