"""Jaxpr/closure auditor: structural proofs on the fused decode programs.

The lint leg (``repro.analysis.lint``) checks what the SOURCE says; this
leg checks what the COMPILER actually built.  The two failure modes it
exists for are silent by construction:

  * a pool or arena buffer captured by closure instead of passed as an
    argument traces fine and runs fine — but the buffer is baked into
    the executable as a constant, so every recompile embeds a stale
    snapshot and the donated in-place update quietly stops being shared;
  * ``donate_argnums`` is a REQUEST: XLA drops the input-output alias
    silently when a shape/layout mismatch prevents in-place reuse, and
    the only artifact is a second pool-sized allocation per dispatch.

Checks (each finding carries its CPAxx id):

  CPA01  closure-captured constant: tracing the step body yields a
         jaxpr const at or above ``max_const_bytes`` (pool/arena-sized
         data must arrive as parameters, never as baked constants)
  CPA02  dropped donation: the compiled ``HloModule`` header's
         ``input_output_alias`` does not alias the pool parameter
  CPA03  mid-program host transfer: ``host_transfer_count`` > 0 under
         the entry computation (outfeed/infeed/send/recv)
  CPA04  dispatch structure: the while-loop nesting does not match
         ``control.dispatch_count``'s one-dispatch claim — K>1 needs a
         depth-0 while of trip K wrapping the depth-1 layer scan; K=1
         needs the depth-0 layer scan

CLI (the CI static-analysis job): builds the smoke-scale MoE fixture,
compiles the single-step and K=4 fused programs, audits both::

    python -m repro.analysis.jaxpr_audit [--k 4] [-v]
"""
from __future__ import annotations

import argparse
import sys
from dataclasses import dataclass
from typing import List, Optional, Sequence

from repro.launch import hlo_analysis as ha

CHECKS = {
    "CPA01": "pool/arena-sized closure constant baked into the program",
    "CPA02": "donate_argnums dropped: pool parameter not aliased in-place",
    "CPA03": "mid-program host transfer inside a fused body",
    "CPA04": "while-loop structure contradicts the dispatch-count claim",
}

#: Consts this large can only be pool/arena/weight data; the legitimate
#: trace-time consts (iota bases, masks for tiny smoke vocabularies)
#: stay well under it.
DEFAULT_MAX_CONST_BYTES = 64 * 1024


@dataclass(frozen=True)
class AuditFinding:
    check: str                 # CPAxx
    target: str                # which program / which object
    message: str

    def __str__(self) -> str:
        return f"{self.target}: {self.check} {self.message}"


# ---------------------------------------------------------------------------
# CPA01: closure-captured constants
# ---------------------------------------------------------------------------

def audit_closure(fn, args: Sequence, *, target: str = "step",
                  max_const_bytes: int = DEFAULT_MAX_CONST_BYTES
                  ) -> List[AuditFinding]:
    """Trace ``fn(*args)`` and flag large jaxpr consts.

    ``fn`` is the UNJITTED body (``jitted.__wrapped__``): anything the
    trace closes over — instead of receiving through ``args`` — lands in
    ``ClosedJaxpr.consts`` and is baked into every executable built from
    the trace.
    """
    import jax
    import numpy as np

    closed = jax.make_jaxpr(fn)(*args)
    findings = []
    for c in closed.consts:
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None:
            nbytes = np.asarray(c).nbytes
        if nbytes >= max_const_bytes:
            shape = getattr(c, "shape", ())
            findings.append(AuditFinding(
                "CPA01", target,
                f"closure-captured constant of {nbytes} bytes "
                f"(shape {tuple(shape)}) baked into the trace — pass it "
                f"as an argument so updates flow and donation can alias "
                f"it"))
    return findings


# ---------------------------------------------------------------------------
# CPA02..CPA04: compiled-HLO structure
# ---------------------------------------------------------------------------

def audit_hlo(hlo: str, *, pool_param: int, n_layers: int, k: int = 1,
              target: str = "step") -> List[AuditFinding]:
    """Audit one compiled module's text (donation, transfers, loops).

    ``pool_param`` is the FLATTENED entry-parameter number of the donated
    pool buffer (see :func:`flat_param_index`); ``k`` is the program's
    decode-steps-per-dispatch.  Every fused step donates its pool on every
    backend, so a pool parameter without an alias is always a finding.
    """
    findings = []
    donated = ha.donated_params(hlo)
    if pool_param not in donated:
        findings.append(AuditFinding(
            "CPA02", target,
            f"pool parameter {pool_param} is not input-output aliased "
            f"(aliased params: {donated or 'none'}) — XLA dropped the "
            f"donation, every dispatch double-buffers the pool"))
    transfers = ha.host_transfer_count(hlo)
    if transfers:
        findings.append(AuditFinding(
            "CPA03", target,
            f"{transfers} mid-program host transfer op(s) under ENTRY — "
            f"the fused body must stay on device end to end"))
    trips = ha.while_trip_structure(hlo)
    if k > 1:
        ok = (0, k) in trips and (1, n_layers) in trips
        want = f"a depth-0 while of trip {k} wrapping a depth-1 " \
               f"{n_layers}-trip layer scan"
    else:
        ok = (0, n_layers) in trips
        want = f"a depth-0 {n_layers}-trip layer scan"
    if not ok:
        findings.append(AuditFinding(
            "CPA04", target,
            f"while structure {trips} lacks {want} — the one-dispatch "
            f"claim of control.dispatch_count does not hold for this "
            f"program"))
    return findings


def flat_param_index(args: Sequence, argnum: int) -> int:
    """Flattened entry-parameter number of positional arg ``argnum``.

    jit flattens pytree arguments into one parameter per leaf, in
    order; the pool is positional arg 4 of the fused steps but its HLO
    parameter number is offset by every leaf of the params pytree ahead
    of it.
    """
    import jax

    return sum(len(jax.tree_util.tree_leaves(a)) for a in args[:argnum])


def audit_fused_step(step, args: Sequence, *, n_layers: int, k: int = 1,
                     pool_argnum: int = 4, target: str = "step",
                     max_const_bytes: int = DEFAULT_MAX_CONST_BYTES
                     ) -> List[AuditFinding]:
    """Full audit of one fused step object (``PagedFusedStep`` /
    ``MultiStepFusedStep``): closure trace + compiled-HLO structure.

    Every fused step donates its pool on every backend, so CPA02 is
    always checked."""
    findings = audit_closure(step._step.__wrapped__, args, target=target,
                             max_const_bytes=max_const_bytes)
    hlo = step._step.lower(*args).compile().as_text()
    findings += audit_hlo(hlo, pool_param=flat_param_index(args, pool_argnum),
                          n_layers=n_layers, k=k, target=target)
    return findings


# ---------------------------------------------------------------------------
# CLI fixture: the smoke-scale MoE colocation cell
# ---------------------------------------------------------------------------

def build_and_audit(k: int = 4, *, batch: int = 2, seq: int = 8
                    ) -> List[AuditFinding]:
    """Build the smoke MoE model's fused programs and audit both
    lowerings: the single-step program and the K-step program."""
    import math

    import jax
    import jax.numpy as jnp

    from repro.configs import PAPER_COLOC_SET, get_smoke_config
    from repro.core.control import MultiStepFusedStep, PagedFusedStep
    from repro.core.pools import build_pools
    from repro.models import build_model
    from repro.runtime.sampler import sample

    name = next(n for n in PAPER_COLOC_SET if get_smoke_config(n).is_moe)
    cfg = get_smoke_config(name).replace(dtype="float32")
    models = {name: cfg}
    model = build_model(cfg)
    params = {name: model.init(jax.random.PRNGKey(0))}
    kv_pool, _, pooled = build_pools(models, params, page_budget=256,
                                     page_bytes=4096,
                                     pool_dtype=jnp.float32)
    virt = kv_pool.virtualizer
    for b in range(batch):
        virt.register_request(b, name, seq)
        virt.reserve_decode_block(b, max(k, 1))
    view = virt.views[name]
    max_pages = max(1, math.ceil((seq + k) / view.tokens_per_page))
    tables = virt.batch_tables(name, list(range(batch)), max_pages)
    tokens = jnp.zeros((batch,), jnp.int32)
    lengths = jnp.full((batch,), seq, jnp.int32)

    findings: List[AuditFinding] = []

    one = PagedFusedStep(pooled[name], postprocess=sample)
    abuf, slot_table = pooled[name].arena.acquire(name)
    findings += audit_fused_step(
        one, (one._p_kv, abuf, slot_table, tokens, virt.pool, tables,
              lengths),
        n_layers=cfg.n_layers, k=1, target="PagedFusedStep")

    if k > 1:
        multi = MultiStepFusedStep(pooled[name], k=k)
        abuf, slot_table = pooled[name].arena.acquire(name)
        findings += audit_fused_step(
            multi, (multi._p_kv, abuf, slot_table, tokens, virt.pool,
                    tables, lengths, jnp.full((batch,), k, jnp.int32),
                    jnp.full((batch,), -1, jnp.int32),
                    jax.random.PRNGKey(0)),
            n_layers=cfg.n_layers, k=k, target=f"MultiStepFusedStep(k={k})")
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.jaxpr_audit",
        description="Structural audit of the compiled fused decode "
                    "programs (CPA01..CPA04).")
    ap.add_argument("--k", type=int, default=4,
                    help="decode steps per dispatch for the multi-step "
                         "program (default 4)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    findings = build_and_audit(args.k)
    for f in findings:
        print(f)
    n = len(findings)
    print(f"repro.analysis.jaxpr_audit: {n} finding{'s' if n != 1 else ''} "
          f"(single-step + k={args.k} programs)")
    if args.verbose and not n:
        for cid, desc in CHECKS.items():
            print(f"  {cid}: clean — {desc}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
