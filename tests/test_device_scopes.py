"""Device scopes and program names: every op of the served programs is
charged to a named stage.

The fused decode step and the prompt-pass stage programs open
``jax.named_scope`` stage scopes (``split_exec.SCOPES``) and compile under
``decode_<model>`` / ``prefill_<stage>_<model>``; a profiler trace reads
the scope of each device op from its ``op_name`` metadata.  These tests
lower the programs at smoke widths, one model per attention kind, and
check the metadata of every HLO instruction.  (Instructions the compiler
adds later, such as layout copies, may carry none: the trace reduction
counts them as ``unscoped``.)
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.control import (MultiStepFusedStep, StreamingPrefill,
                                program_name)
from repro.core.pools import build_pools
from repro.core.split_exec import SCOPES
from repro.core.virtualizer import _pool_pair_scatter
from repro.models import build_model

#: Instructions that carry no computation of their own.
EXEMPT = {"parameter", "constant", "tuple", "get-tuple-element"}
#: Ops whose ``to_apply`` computation is a part of the op (a reducer, a
#: scatter's combiner), not a program of its own.
REDUCERS = {"reduce", "reduce-window", "scatter", "select-and-scatter",
            "sort", "map", "all-reduce", "reduce-scatter"}

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(to_apply|body|condition|calls|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _opcode(rest: str) -> str:
    """The opcode of an HLO instruction line's right-hand side (after its
    shape, which may be a parenthesised tuple)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
    shape_end = rest.index(" ", i)
    return rest[shape_end + 1:].split("(", 1)[0].strip()


def _parse(hlo_text: str):
    """{computation: [(name, opcode, op_name, rest)]} and the entry's
    name."""
    comps, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None and " = " not in line:
            current = head.group(2)
            comps[current] = []
            if head.group(1):
                entry = current
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        op_name = _OP_NAME.search(line)
        comps[current].append((m.group(1), _opcode(m.group(2)),
                               op_name.group(1) if op_name else "",
                               m.group(2)))
    return comps, entry


def unscoped_instructions(hlo_text: str):
    """(instruction, opcode, op_name) of every instruction of a lowered
    program that names none of the scopes, where an instruction of a
    called computation (a loop body, a nested ``jit``) also carries the
    stage scopes of the instructions that call it, as the compiler's
    inlining gives it them.  Parameters, constants, tuples, broadcasts of a
    constant and the inside of reducers are not ops of their own."""
    comps, entry = _parse(hlo_text)
    scopes = set(SCOPES)
    # what a called computation inherits: a stage, not the loop wrapper
    # (the ``loop`` scope names the loop's own control alone)
    stages = scopes - {"loop"}
    callers = {}                      # computation -> [calling in a stage?]
    reducer_bodies = set()

    def walk(comp, inherited):
        for name, op, op_name, rest in comps[comp]:
            scoped = inherited or bool(set(op_name.split("/")) & stages)
            called = [c for _, c in _CALLEE.findall(rest)]
            for b in _BRANCHES.findall(rest):
                called += [c.strip().lstrip("%") for c in b.split(",")]
            for c in called:
                if op in REDUCERS:
                    reducer_bodies.add(c)
                elif c in comps:
                    callers.setdefault(c, []).append(scoped)
                    walk(c, scoped)

    walk(entry, False)
    out = []
    for comp, instrs in comps.items():
        if comp in reducer_bodies:
            continue
        inherited = bool(callers.get(comp)) and all(callers[comp])
        constants = set()
        for name, op, op_name, rest in instrs:
            if op == "constant" or (op == "broadcast" and rest.split(
                    "broadcast(", 1)[1].split(")")[0] in constants):
                constants.add(name)
                continue
            if op in EXEMPT or inherited:
                continue
            if not set(op_name.split("/")) & scopes:
                out.append((name, op, op_name or None))
    return out


def _hlo(lowered) -> str:
    return lowered.as_text(dialect="hlo", debug_info=True)


def _module_name(hlo_text: str) -> str:
    return hlo_text.split()[1].rstrip(",")


def _setup(name):
    cfg = get_smoke_config(name).replace(dtype="float32")
    params = {name: build_model(cfg).init(jax.random.PRNGKey(0))}
    kv_pool, _, pooled = build_pools({name: cfg}, params, page_budget=64,
                                     page_bytes=4096, pool_dtype=jnp.float32)
    return cfg, pooled[name], kv_pool.virtualizer


@pytest.fixture(scope="module", params=["qwen3-moe-235b-a22b", "minicpm3-4b"])
def model(request):
    return _setup(request.param)


def test_fused_decode_step_ops_all_scoped(model):
    cfg, pooled, virt = model
    step = MultiStepFusedStep(pooled, k=2)
    B = 2
    max_pages = max(1, math.ceil(32 / virt.views[cfg.name].tokens_per_page))
    abuf, slot_table = pooled.arena.acquire(cfg.name)
    i32 = jnp.int32
    lowered = step._step.lower(
        step._p_kv, abuf, slot_table, jnp.zeros((B,), i32), virt.pool,
        jnp.zeros((cfg.n_layers, B, max_pages), i32), jnp.ones((B,), i32),
        jnp.ones((B,), i32), jnp.full((B,), -1, i32),
        jax.random.PRNGKey(0))
    text = _hlo(lowered)
    assert _module_name(text) == "jit_" + program_name("decode", cfg.name)
    assert unscoped_instructions(text) == []
    # the stage scopes the decode metrics read are all there
    names = set("/".join(_OP_NAME.findall(text)).split("/"))
    want = {"embed", "attn", "kv_write", "kv_view", "attention", "ffn",
            "unpack", "head", "loop"}
    want |= {"router", "experts"} if cfg.is_moe else {"mlp"}
    assert want <= names


def test_prompt_pass_programs_all_scoped(model):
    cfg, pooled, virt = model
    sp = StreamingPrefill(pooled)
    B, S = 2, 8
    p_kv = pooled.kv_params
    abuf, slot_table = pooled.arena.acquire(cfg.name)
    tokens = jnp.zeros((B, S), jnp.int32)
    x = jax.eval_shape(sp._embed, p_kv, tokens)
    _, ffn_in, layer_kv = jax.eval_shape(sp._attn, p_kv, x, 0)
    ffn_out = jax.eval_shape(sp._ffn, abuf, slot_table, ffn_in, 0)
    programs = {
        "prefill_embed": sp._embed.lower(p_kv, tokens),
        "prefill_attn": sp._attn.lower(p_kv, x, 0),
        "prefill_ffn": sp._ffn.lower(abuf, slot_table, ffn_in, 0),
        "prefill_combine": sp._combine.lower(x, ffn_out),
        "prefill_logits": sp._logits.lower(p_kv, x,
                                           jnp.zeros((B,), jnp.int32)),
    }
    for kind, lowered in programs.items():
        text = _hlo(lowered)
        assert _module_name(text) == "jit_" + program_name(kind, cfg.name)
        assert unscoped_instructions(text) == [], kind
    idx = jnp.zeros((S,), jnp.int32)
    write = _pool_pair_scatter.lower(
        virt.pool, *layer_kv, idx, idx, n_tokens=S,
        batch_index=1, mla=cfg.attention == "mla")
    assert unscoped_instructions(_hlo(write)) == []


def test_unscoped_instructions_finds_ops_outside_scopes():
    def f(x):
        with jax.named_scope("attn"):
            y = jnp.sin(x)
        return y * jnp.cos(x)

    bad = unscoped_instructions(_hlo(jax.jit(f).lower(jnp.ones(4))))
    assert {op for _, op, _ in bad} == {"cosine", "multiply"}
