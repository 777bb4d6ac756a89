"""Bytes the FFN stage of the traced decode-only steps must move
(``cb.stage_work``: router, the chosen experts' slabs, dense FFN), over
the device self time under the ``ffn`` scope in those steps
(``cb.scopes``: the arena unpack, router, experts) and the chip's HBM
bandwidth, as a percentage."""
from cb.stage_work import stage_roofline


def read(run):
    return stage_roofline(run, "ffn")
