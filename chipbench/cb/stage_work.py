"""The bytes of one decode step (``cb.work``) split by the stage that
moves them, for the stage rooflines.

Per model and step, with the same counts as ``ModelShape.decode_step``:

* ``attn``: every attention weight, the two norms of each layer, and the
  KV rows read (``ctx + 1`` per request and layer) and written (one);
* ``ffn``: the router, the slabs of the distinct experts the batch chose
  (or the dense FFN);
* ``head``: the final norm, the unembedding and the embedding rows looked
  up.

The three parts sum to ``decode_step_work``'s bytes.  A stage's share of
the HBM roofline (:func:`stage_roofline`) divides its bytes over the
traced decode-only steps by the device self time under its scope
(``cb.scopes``) in those steps and the chip's bandwidth.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from cb.scopes import decode_only_scopes, seconds_under
from cb.work import ModelShape

STAGES = ("attn", "ffn", "head")


def stage_bytes(m: ModelShape, ctx: Sequence[int]) -> Dict[str, float]:
    """Bytes per stage of one decode step of one model over requests at
    context lengths ``ctx``."""
    B = len(ctx)
    if B == 0:
        return dict.fromkeys(STAGES, 0.0)
    d, L, w = m.d_model, m.n_layers, m.weight_bytes
    kv_tok = m.kv_elems_per_token() * m.kv_bytes
    kv = sum(c + 1 for c in ctx) * kv_tok + B * kv_tok       # read + write
    if m.ffn == "moe":
        ffn = (d * m.n_experts * m.router_bytes
               + m.distinct_experts(B) * m.expert_params() * w)
    else:
        ffn = m.expert_params() * w
    return {"attn": float(L * (m.attn_params() * w + 2 * d * w + kv)),
            "ffn": float(L * ffn),
            "head": float(d * w + d * m.vocab * w + B * d * w)}


def decode_step_stage_bytes(shapes: Dict[str, ModelShape],
                            ctx_by_model: Dict[str, Sequence[int]]
                            ) -> Dict[str, float]:
    """Bytes per stage of one engine decode step over every model."""
    out = dict.fromkeys(STAGES, 0.0)
    for name, ctx in ctx_by_model.items():
        for k, v in stage_bytes(shapes[name], ctx).items():
            out[k] += v
    return out


def stage_roofline(run: Dict, stage: str) -> Optional[float]:
    """Percent of the HBM roofline one stage reaches over the traced
    decode-only steps, or None where the trace has no scopes."""
    steps = decode_only_scopes(run)
    secs = sum(seconds_under(sc, stage) for _, sc in steps)
    if secs <= 0:
        return None
    nb = sum(decode_step_stage_bytes(run["shapes"], s.ctx)[stage]
             for s, _ in steps)
    return 100.0 * nb / (secs * run["peaks"]["hbm_bytes_per_s"]
                         * run["chips_used"])
