"""Plain float32 reference of the benchmark's models, and its control.

Written from the configuration files alone: it imports nothing of the
program and takes only the benchmark's own seeded weights.  One request at
a time, padded to a fixed length so that one program serves every request;
causal attention keeps the padding from reaching the positions compared.

Semantics (the configuration as the repository runs it):

* decoder of ``n_layers`` blocks ``x += attn(norm1(x)); x += ffn(norm2(x))``,
  RMSNorm with float32 statistics and scale ``1 + w``, a final norm and an
  untied head;
* RoPE on pairs ``(i, i + dim/2)`` ("rotate half");
* ``gqa``: ``n_heads`` queries over ``n_kv_heads`` keys/values of
  ``head_dim``, RoPE on the whole head, scale ``head_dim ** -0.5``;
* ``mla``: query LoRA (``q_lora_rank``, RMSNorm), a KV latent of
  ``kv_lora_rank`` (RMSNorm) plus a shared RoPE key of
  ``qk_rope_head_dim``, heads of ``qk_nope + qk_rope`` for scores and
  ``v_head_dim`` for values, scale ``(qk_nope + qk_rope) ** -0.5``;
* ``dense`` FFN: SwiGLU of ``d_ff``;
* ``moe`` FFN: float32 softmax router over ``n_experts``, the top
  ``experts_per_token`` renormalised, SwiGLU experts of ``d_ff``.  A
  prompt runs as the engine runs a prompt alone: an expert takes at most
  ``C`` of its tokens (``C`` from the prompt's bucket and
  ``capacity_factor``, in token order, choices ranked by probability) and
  a pair beyond ``C`` adds nothing.  A generated token runs as one token
  alone does: no pair is dropped.

Every matrix product runs at ``precision=HIGHEST``.  A control runs the
same code with both operands of every matrix product rounded to a format
below the bfloat16 the configuration states, each scaled to its range
(weights per output channel, activations per row): ``int8`` (symmetric,
127 steps) or ``fp8`` (``float8_e4m3fn``: 3 mantissa bits, largest 448,
subnormals below 2**-6); and the float32 router's product at
``precision=HIGH`` (three bf16 passes), the step below the ``highest`` it
is stated at.

The interface of a reference module.  A model's entry in a configuration
file names its reference under ``reference`` (a module under
``chipbench/cb/``; this one where it names none), which provides:

* ``Reference(as_run, length, max_ctx, mode)``: the model that one
  ``as_run`` block describes, over requests padded to ``length`` tokens,
  a prompt running as the engine runs it under ``max_ctx``; ``mode`` is
  ``"f32"`` or a control.  ``hidden(params, ids, plen)`` gives the float32
  hidden state after the final norm, ``[length, d]``, of ``ids`` whose
  first ``plen`` are the prompt; ``argmax(params, x)`` each position's
  first token under ``mode``, ``[length]``; ``gaps(params, x, targets)``
  each target token's gap in float32, ``[length, T]``.  ``params`` are the
  benchmark's seeded weights in the engine's layout.
* ``served_gaps(ref, params, prompt, served, controls)``: the gaps at the
  served tokens' positions, ``[n, 1 + len(controls)]`` (below).
* ``QUANTIZERS``: the control modes, each a name and its rounding.
* ``KEYS``: the ``as_run`` keys it computes; the harness refuses a model
  with a key outside them before anything is built.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512)


def prompt_bucket(n: int, max_ctx: int) -> int:
    """The padded length a prompt of ``n`` tokens runs at (smallest bucket
    that holds it, else ``max_ctx``)."""
    for b in PREFILL_BUCKETS:
        if n <= b <= max_ctx:
            return b
    return max_ctx


def expert_capacity(n_tokens: int, k: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Tokens one expert takes in a pass of ``n_tokens`` (sublane-rounded
    to a multiple of 8 above 8)."""
    c = math.ceil(n_tokens * k * capacity_factor / n_experts)
    return max(8, ((c + 7) // 8) * 8) if c > 8 else max(c, 1)


# ---------------------------------------------------------------------------
# precision of the matrix products
# ---------------------------------------------------------------------------

def _q_int8(a: jax.Array, axis: int) -> jax.Array:
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def _q_fp8(a: jax.Array, axis: int) -> jax.Array:
    """Round to ``float8_e4m3fn`` after scaling the largest magnitude along
    ``axis`` to 448, then scale back (float32 arithmetic throughout)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    y = a / s
    _, e = jnp.frexp(y)                        # |y| in [2**(e-1), 2**e)
    ulp = jnp.exp2(jnp.maximum(e - 1, -6).astype(jnp.float32) - 3.0)
    return jnp.clip(jnp.round(y / ulp) * ulp, -448.0, 448.0) * s


QUANTIZERS = {"int8": _q_int8, "fp8": _q_fp8}

#: The ``as_run`` keys this reference computes (a width it takes from the
#: weights, such as ``d_model`` or ``vocab``, is among them).
KEYS = frozenset({
    "n_layers", "d_model", "vocab", "attention", "n_heads", "n_kv_heads",
    "head_dim", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "ffn", "d_ff", "n_experts",
    "experts_per_token", "capacity_factor", "rope_theta", "norm_eps"})


def mm(x: jax.Array, w: jax.Array, mode: str,
       spec: str = "...d,df->...f") -> jax.Array:
    """A matrix product in float32 (``mode="f32"``) or with operands rounded
    to a control's format (``QUANTIZERS``): ``x`` per row (its last axis),
    ``w`` per output column (its second-to-last axis)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode != "f32":
        x = QUANTIZERS[mode](x, -1)
        w = QUANTIZERS[mode](w, -2)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [..., S, H, D]; pos [S]."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv          # [S, D/2]
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def softmax_attend(q, k, v, scale: float) -> jax.Array:
    """q [S,H,D]; k [S,H,D]; v [S,H,Dv]; causal."""
    S = q.shape[0]
    sc = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * scale
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    sc = jnp.where(mask[None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("hst,thd->shd", w, v, precision=HIGHEST)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

def attention(p: Dict, m: Dict, h: jax.Array, mode: str) -> jax.Array:
    S = h.shape[0]
    pos = jnp.arange(S)
    H = m["n_heads"]
    if m["attention"] == "gqa":
        hd, kv = m["head_dim"], m["n_kv_heads"]
        q = mm(h, p["wq"], mode).reshape(S, H, hd)
        k = mm(h, p["wk"], mode).reshape(S, kv, hd)
        v = mm(h, p["wv"], mode).reshape(S, kv, hd)
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
        k = jnp.repeat(k, H // kv, axis=1)
        v = jnp.repeat(v, H // kv, axis=1)
        o = softmax_attend(q, k, v, hd ** -0.5).reshape(S, H * hd)
        return mm(o, p["wo"], mode)
    nope, rp = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, vd = m["kv_lora_rank"], m["v_head_dim"]
    cq = rms(mm(h, p["wdq"], mode), p["q_ln"], m["norm_eps"])
    q = mm(cq, p["wuq"], mode).reshape(S, H, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos,
                                             m["rope_theta"])], axis=-1)
    ckv = mm(h, p["wdkv"], mode)
    lat = rms(ckv[:, :r], p["kv_ln"], m["norm_eps"])
    k_rope = rope(ckv[:, None, r:], pos, m["rope_theta"])       # [S,1,rp]
    k_nope = mm(lat, p["wuk"], mode).reshape(S, H, nope)
    v = mm(lat, p["wuv"], mode).reshape(S, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (S, H, rp))],
                        axis=-1)
    o = softmax_attend(q, k, v, (nope + rp) ** -0.5).reshape(S, H * vd)
    return mm(o, p["wo"], mode)


def swiglu(p: Dict, h: jax.Array, mode: str) -> jax.Array:
    return mm(jax.nn.silu(mm(h, p["wg"], mode)) * mm(h, p["wu"], mode),
              p["wd"], mode)


def moe(p: Dict, m: Dict, h: jax.Array, plen, cap, mode: str):
    """Routed experts over every position of ``h`` [S, d]; positions below
    ``plen`` are the prompt and keep at most ``cap`` tokens per expert."""
    S = h.shape[0]
    E, k = m["n_experts"], m["experts_per_token"]
    # the router is stated in float32 at the highest precision: the step
    # below it is three bf16 passes
    logits = jnp.matmul(h, p["router"].astype(jnp.float32),
                        precision=HIGHEST if mode == "f32" else HIGH)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)                   # [S, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)       # [S, k, E]
    in_prompt = (jnp.arange(S) < plen)[:, None, None]
    flat = (onehot * in_prompt).reshape(S * k, E)
    before = (jnp.cumsum(flat, axis=0) - flat).reshape(S, k, E)
    slot = jnp.sum(before * onehot, axis=-1)                   # [S, k]
    keep = (slot < cap) | ~in_prompt[..., 0]
    weight = jnp.sum(onehot * (gates * keep)[..., None], axis=1)  # [S, E]
    g = mm(h, p["wg"], mode, "sd,edf->esf")
    u = mm(h, p["wu"], mode, "sd,edf->esf")
    a = jax.nn.silu(g) * u * weight.T[:, :, None]              # [E, S, f]
    return mm(a, p["wd"], mode, "esf,efd->sd")


def layer(p: Dict, m: Dict, x: jax.Array, plen, cap, mode: str):
    x = x + attention(p["attn"], m, rms(x, p["ln1"], m["norm_eps"]), mode)
    h = rms(x, p["ln2"], m["norm_eps"])
    if m["ffn"] == "moe":
        return x + moe(p["moe"], m, h, plen, cap, mode)
    return x + swiglu(p["mlp"], h, mode)


# ---------------------------------------------------------------------------
# a whole request
# ---------------------------------------------------------------------------

class Reference:
    """The reference of one model: ``hidden(params, ids, plen)`` gives the
    float32 hidden state after the final norm; ``argmax`` and ``gaps``
    read logits."""

    def __init__(self, m: Dict, length: int, max_ctx: int, mode: str = "f32"):
        self.m, self.length, self.max_ctx, self.mode = m, length, max_ctx, mode
        self._layer = jax.jit(lambda p, x, plen, cap: layer(
            p, m, x, plen, cap, mode))
        self._embed = jax.jit(lambda tok, ids: tok[ids].astype(jnp.float32))
        self._final = jax.jit(lambda w, x: rms(x, w, m["norm_eps"]))

        def argmax(head, x):
            return jnp.argmax(mm(x, head, mode), axis=-1).astype(jnp.int32)

        def gaps(head, x, targets):
            lg = mm(x, head, "f32")                            # [S, V]
            best = jnp.max(lg, axis=-1)
            sd = jnp.std(lg, axis=-1)
            got = jnp.take_along_axis(lg, targets, axis=-1)     # [S, T]
            return (best[:, None] - got) / sd[:, None]

        self._argmax = jax.jit(argmax)
        self._gaps = jax.jit(gaps)

    def hidden(self, params: Dict, ids: Sequence[int], plen: int):
        m = self.m
        S = self.length
        pad = np.zeros(S, np.int32)
        pad[: len(ids)] = ids
        cap = 0
        if m["ffn"] == "moe":
            cap = expert_capacity(prompt_bucket(plen, self.max_ctx),
                                  m["experts_per_token"], m["n_experts"],
                                  m["capacity_factor"])
        x = self._embed(params["embed"]["tok"], jnp.asarray(pad))
        for li in range(m["n_layers"]):
            p_l = jax.tree.map(lambda a: a[li], params["layers"])
            x = self._layer(p_l, x, jnp.int32(plen), jnp.int32(cap))
        return self._final(params["final_norm"], x)

    def argmax(self, params: Dict, x) -> np.ndarray:
        return np.asarray(self._argmax(params["embed"]["head"], x))

    def gaps(self, params: Dict, x, targets: np.ndarray) -> np.ndarray:
        """[S, T] gap of each target token below the best logit, in units
        of the standard deviation of that position's logits."""
        return np.asarray(self._gaps(params["embed"]["head"], x,
                                     jnp.asarray(targets, jnp.int32)))


def served_gaps(ref: Reference, params: Dict, prompt: Sequence[int],
                served: Sequence[int], controls: Sequence[Reference] = ()
                ) -> np.ndarray:
    """Gaps under the reference at the positions of the served tokens,
    ``[n, 1 + len(controls)]``: of the served tokens themselves, then of the
    token each control puts first at those positions (teacher-forced on the
    same prompt and served tokens)."""
    P, n = len(prompt), len(served)
    ids = list(prompt) + list(served[:-1])
    pos = np.arange(P - 1, P - 1 + n)
    tgt = np.zeros((ref.length, 1 + len(controls)), np.int32)
    tgt[pos, 0] = served
    for j, ctl in enumerate(controls):
        tgt[:, 1 + j] = ctl.argmax(params, ctl.hidden(params, ids, P))
    return ref.gaps(params, ref.hidden(params, ids, P), tgt)[pos]
