"""Bytes and operations a decode step must move and compute, from shapes.

This counts the WORK the models require for the requests actually in the
batch, not what an implementation happens to do: a whole-pool relayout,
padding rows of an unfilled batch or experts that no token chose are not
counted.  So a change that removes such waste cannot push a share of the
roofline above 100%.

Per model and decode step, with B active requests at context lengths
``ctx`` (tokens already cached; the step's token lands at position ctx):

* bytes: every attention weight and norm, the router, the expert slabs of
  the distinct experts the B tokens chose (expected count under uniform
  routing, ``E * (1 - (1 - k/E) ** B)``, because the engine does not expose
  its decode routes), the dense FFN weights, the unembedding, the KV rows
  read (``ctx + 1`` per request and layer) and written (one per request
  and layer), and the embedding rows looked up;
* operations: 2 per multiply-add of every projection, the router, the k
  chosen experts and the unembedding, and attention at the actual context:
  GQA ``4 * H * head_dim * (ctx + 1)`` per layer; MLA in its absorbed form
  (queries absorbed into the latent, scores over ``latent + rope`` rows,
  context over the latent).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

#: ``as_run`` keys that change no byte or operation of a decode step.
WORK_NEUTRAL = frozenset({"rope_theta", "norm_eps", "capacity_factor"})


@dataclass(frozen=True)
class ModelShape:
    """The sizes of one model as it is run (``as_run`` in a config file)."""

    name: str
    n_layers: int
    d_model: int
    vocab: int
    attention: str                 # "gqa" | "mla"
    n_heads: int
    ffn: str                       # "moe" | "dense"
    d_ff: int                      # per expert for MoE
    n_kv_heads: int = 0
    head_dim: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_experts: int = 0
    experts_per_token: int = 0
    weight_bytes: int = 2          # bf16
    router_bytes: int = 4          # the router is kept in float32
    kv_bytes: int = 2              # the pool's element size

    @classmethod
    def from_dict(cls, name: str, d: Dict) -> "ModelShape":
        """The shape of one ``as_run`` block; a key that is neither counted
        nor in :data:`WORK_NEUTRAL` is refused, so no count ignores part of
        the model."""
        keys = set(cls.__dataclass_fields__) - {"name"}
        unknown = sorted(set(d) - keys - WORK_NEUTRAL)
        if unknown:
            raise ValueError(f"{name}: cb.work.ModelShape does not count "
                             f"{unknown}")
        return cls(name=name, **{k: v for k, v in d.items() if k in keys})

    # ---- per-layer parameter counts --------------------------------------
    def attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if self.attention == "gqa":
            hd, kv = self.head_dim, self.n_kv_heads
            return d * H * hd + 2 * d * kv * hd + H * hd * d
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        r, rp = self.kv_lora_rank, self.qk_rope_head_dim
        q = (d * self.q_lora_rank + self.q_lora_rank * H * qk
             + self.q_lora_rank) if self.q_lora_rank else d * H * qk
        return (q + d * (r + rp) + r + r * H * self.qk_nope_head_dim
                + r * H * self.v_head_dim + H * self.v_head_dim * d)

    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    def kv_elems_per_token(self) -> int:
        """Pool elements one token holds in one layer."""
        if self.attention == "gqa":
            return 2 * self.n_kv_heads * self.head_dim
        return self.kv_lora_rank + self.qk_rope_head_dim

    def distinct_experts(self, batch: int) -> float:
        E, k = self.n_experts, self.experts_per_token
        return E * (1.0 - (1.0 - k / E) ** batch)

    # ---- one decode step ---------------------------------------------------
    def attn_flops_per_token(self, ctx: int) -> float:
        """Operations of one token's attention sub-block in one layer."""
        H, n = self.n_heads, ctx + 1
        if self.attention == "gqa":
            return 2.0 * self.attn_params() + 4.0 * H * self.head_dim * n
        # absorbed form: wuk multiplies the query and wuv the context, one
        # multiply-add per weight each, as the projections count them
        r, rp = self.kv_lora_rank, self.qk_rope_head_dim
        return (2.0 * self.attn_params()
                + 2.0 * H * (r + rp) * n + 2.0 * H * r * n)

    def ffn_flops_per_token(self) -> float:
        if self.ffn == "moe":
            return (2.0 * self.d_model * self.n_experts
                    + self.experts_per_token * 2.0 * self.expert_params())
        return 2.0 * self.expert_params()

    def decode_step(self, ctx: Sequence[int]) -> Tuple[float, float]:
        """(bytes, operations) of one decode step over requests at context
        lengths ``ctx``.  An empty batch costs nothing."""
        B = len(ctx)
        if B == 0:
            return 0.0, 0.0
        d, L, w = self.d_model, self.n_layers, self.weight_bytes
        per_layer = self.attn_params() * w + 2 * d * w          # + 2 norms
        if self.ffn == "moe":
            per_layer += (d * self.n_experts * self.router_bytes
                          + self.distinct_experts(B) * self.expert_params()
                          * w)
        else:
            per_layer += self.expert_params() * w
        kv_tok = self.kv_elems_per_token() * self.kv_bytes
        kv = sum(c + 1 for c in ctx) * kv_tok + B * kv_tok       # read+write
        nbytes = (L * (per_layer + kv) + d * w                    # final norm
                  + d * self.vocab * w + B * d * w)               # head, embed
        flops = sum(L * (self.attn_flops_per_token(c)
                         + self.ffn_flops_per_token())
                    + 2.0 * d * self.vocab for c in ctx)
        return float(nbytes), float(flops)


def decode_step_work(shapes: Dict[str, ModelShape],
                     ctx_by_model: Dict[str, Sequence[int]]
                     ) -> Tuple[float, float]:
    """(bytes, operations) of one engine decode step over every model."""
    nb = nf = 0.0
    for name, ctx in ctx_by_model.items():
        b, f = shapes[name].decode_step(ctx)
        nb += b
        nf += f
    return nb, nf
