"""Checkable invariants (DESIGN.md §12): every rule must actually fire.

Three legs, one deliberate violation per rule:

* lint (CP001..CP007): each rule is fed a minimal source snippet at a
  repo-shaped fake path containing exactly its violation and must report
  exactly that rule id; the pragma escape hatch suppresses it; the REAL
  tree lints clean (the CI gate, asserted here too so a regression fails
  tier-1 before it fails CI);
* jaxpr/HLO audit (CPA01..CPA04): closure capture is caught on a traced
  function, and each HLO check fires on a synthetic module exhibiting
  its violation — plus the donation parser round-trips a real compiled
  donated program;
* shadow sanitizer (SAN01..SAN08): each invariant is violated by
  corrupting a real ``KVVirtualizer``/``WeightArena`` and ``audit()``
  must raise ``PoolSanitizerError`` with that rule id; an engine run
  with the sanitizer attached produces the bit-exact token stream of a
  detached run and reports zero violations.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from repro.analysis.lint import default_roots, lint_paths, lint_source
from repro.analysis import jaxpr_audit as ja
from repro.analysis.sanitizer import PoolSanitizer, PoolSanitizerError
from repro.configs import EngineConfig, PAPER_COLOC_SET, get_smoke_config
from repro.core.virtualizer import KVVirtualizer
from repro.core.weight_pool import Residency, WeightArena

MODEL = sorted(PAPER_COLOC_SET)[0]


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# lint: one deliberate violation per rule
# ---------------------------------------------------------------------------

def test_cp001_host_sync_in_jitted_body():
    src = (
        "import jax\n"
        "def step(x):\n"
        "    y = jax.device_get(x)\n"
        "    return y\n"
        "fast = jax.jit(step)\n"
    )
    assert rules_of(lint_source(src, "src/repro/core/control.py")) \
        == ["CP001"]


def test_cp001_block_until_ready_in_scanned_body():
    src = (
        "import jax\n"
        "def body(c, x):\n"
        "    x.block_until_ready()\n"
        "    return c, x\n"
        "out = jax.lax.scan(body, 0, xs)\n"
    )
    assert "CP001" in rules_of(lint_source(src, "src/repro/core/control.py"))


def test_cp002_sampling_outside_sampler():
    src = "import jax.numpy as jnp\ntok = jnp.argmax(logits, -1)\n"
    assert rules_of(lint_source(src, "src/repro/runtime/engine.py")) \
        == ["CP002"]
    # the canonical module itself is exempt
    assert lint_source(src, "src/repro/runtime/sampler.py") == []


def test_cp003_counter_bump_without_hook():
    src = (
        "class KVVirtualizer:\n"
        "    def swap_out(self, n):\n"
        "        self.swap_out_pages += n\n"
        "        return n\n"
    )
    assert rules_of(lint_source(src, "src/repro/core/virtualizer.py")) \
        == ["CP003"]


def test_cp003_satisfied_by_adjacent_hook():
    src = (
        "class KVVirtualizer:\n"
        "    def swap_out(self, n):\n"
        "        self.swap_out_pages += n\n"
        "        if self.hooks is not None:\n"
        "            self.hooks.kv_swap_out(n)\n"
        "        return n\n"
    )
    assert lint_source(src, "src/repro/core/virtualizer.py") == []


def test_cp004_loose_engine_kwargs():
    src = "eng = CrossPoolEngine(models, mode=EngineMode(), seed=0)\n"
    assert rules_of(lint_source(src, "benchmarks/new_bench.py")) == ["CP004"]
    ok = "eng = CrossPoolEngine(models, config=EngineConfig(), seed=0)\n"
    assert lint_source(ok, "benchmarks/new_bench.py") == []


def test_cp005_adhoc_percentile():
    src = "import numpy as np\np99 = np.percentile(xs, 99)\n"
    assert rules_of(lint_source(src, "src/repro/runtime/engine.py")) \
        == ["CP005"]
    assert lint_source(src, "benchmarks/_stats.py") == []


def test_cp006_wall_clock_in_engine():
    src = "import time\nt0 = time.perf_counter()\n"
    assert rules_of(lint_source(src, "src/repro/runtime/engine.py")) \
        == ["CP006"]
    # same call outside the clock-scoped paths is fine
    assert lint_source(src, "benchmarks/new_bench.py") == []


def test_cp007_bare_assert_in_accounting():
    src = "def f(n):\n    assert n >= 0\n    return n\n"
    assert rules_of(lint_source(src, "src/repro/core/virtualizer.py")) \
        == ["CP007"]
    assert lint_source(src, "src/repro/runtime/engine.py") == []


def test_pragma_suppresses_and_is_line_scoped():
    src = (
        "import time\n"
        "t0 = time.perf_counter()  # cp: allow(CP006) dispatch duration\n"
        "t1 = time.perf_counter()\n"
    )
    found = lint_source(src, "src/repro/runtime/engine.py")
    assert [f.line for f in found] == [3]


def test_syntax_error_reports_cp000():
    assert rules_of(lint_source("def f(:\n", "src/repro/core/x.py")) \
        == ["CP000"]


def test_real_tree_lints_clean():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint_paths(default_roots(repo))
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# jaxpr/HLO audit: one violation per check
# ---------------------------------------------------------------------------

def test_cpa01_closure_captured_constant():
    import jax.numpy as jnp

    baked = jnp.zeros((64, 1024), jnp.float32)       # 256 KiB constant

    def leaky(x):
        return x + baked

    found = ja.audit_closure(leaky, (jnp.zeros((64, 1024), jnp.float32),))
    assert [f.check for f in found] == ["CPA01"]

    def clean(x, pool):
        return x + pool

    assert ja.audit_closure(
        clean, (jnp.zeros((4,)), jnp.zeros((4,)))) == []


SYNTH_NO_ALIAS = """\
HloModule m, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4] parameter(0)
  ROOT %r = f32[4] add(%p0, %p0)
}
"""

SYNTH_ALIASED = """\
HloModule m, input_output_alias={ {0}: (4, {}, may-alias) }, \
entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4] parameter(0)
  %w.1 = f32[4] while(%p0), condition=%cond, body=%body, \
backend_config={"known_trip_count":{"n":"4"}}
  ROOT %r = f32[4] add(%w.1, %w.1)
}

%body (b0: f32[4]) -> f32[4] {
  %b0 = f32[4] parameter(0)
  %w.2 = f32[4] while(%b0), condition=%cond2, body=%body2, \
backend_config={"known_trip_count":{"n":"2"}}
  ROOT %rb = f32[4] add(%w.2, %b0)
}

%body2 (c0: f32[4]) -> f32[4] {
  %c0 = f32[4] parameter(0)
  ROOT %rc = f32[4] add(%c0, %c0)
}
"""

SYNTH_TRANSFER = """\
HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }, \
entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4] parameter(0)
  %t = token[] after-all()
  %s = (f32[4], u32[], token[]) send(%p0, %t), channel_id=1
  %w.1 = f32[4] while(%p0), condition=%cond, body=%body, \
backend_config={"known_trip_count":{"n":"2"}}
  ROOT %r = f32[4] add(%w.1, %w.1)
}

%body (b0: f32[4]) -> f32[4] {
  %b0 = f32[4] parameter(0)
  ROOT %rb = f32[4] add(%b0, %b0)
}
"""


def test_cpa02_dropped_donation_on_synthetic_hlo():
    found = ja.audit_hlo(SYNTH_NO_ALIAS, pool_param=0, n_layers=2, k=1)
    assert "CPA02" in [f.check for f in found]
    # the aliased pool parameter is not a finding
    found = ja.audit_hlo(SYNTH_ALIASED, pool_param=4, n_layers=2, k=4)
    assert "CPA02" not in [f.check for f in found]


def test_cpa03_host_transfer_on_synthetic_hlo():
    found = ja.audit_hlo(SYNTH_TRANSFER, pool_param=0, n_layers=2, k=2)
    assert "CPA03" in [f.check for f in found]


def test_cpa04_dispatch_structure():
    # K=4 over a 2-layer scan: the aliased module has exactly that shape
    assert ja.audit_hlo(SYNTH_ALIASED, pool_param=4, n_layers=2, k=4) == []
    # claiming K=8 must fail structurally
    found = ja.audit_hlo(SYNTH_ALIASED, pool_param=4, n_layers=2, k=8)
    assert [f.check for f in found] == ["CPA04"]
    # a module with no while at all fails the K=1 layer-scan claim too
    found = ja.audit_hlo(SYNTH_NO_ALIAS, pool_param=0, n_layers=2, k=1)
    assert [f.check for f in found] == ["CPA02", "CPA04"]


def test_alias_parser_roundtrips_real_donated_program():
    import jax
    import jax.numpy as jnp

    from repro.launch import hlo_analysis as ha

    f = jax.jit(lambda p, x: p.at[0].add(x), donate_argnums=(0,))
    hlo = f.lower(jnp.zeros((8, 4)), jnp.ones((4,))).compile().as_text()
    assert ha.donated_params(hlo) == [0]
    g = jax.jit(lambda p, x: p + x)
    hlo = g.lower(jnp.zeros((8, 4)), jnp.ones((4,))).compile().as_text()
    assert ha.donated_params(hlo) == []


# ---------------------------------------------------------------------------
# sanitizer: one corruption per invariant
# ---------------------------------------------------------------------------

def make_virt(budget=32):
    virt = KVVirtualizer({MODEL: get_smoke_config(MODEL)},
                         page_budget=budget, page_bytes=4096,
                         allocate_device_pool=False)
    virt.register_request(0, MODEL, 8)
    return virt


def expect_rule(san, rule):
    with pytest.raises(PoolSanitizerError) as ei:
        san.audit()
    assert ei.value.rule == rule, str(ei.value)


def test_sanitizer_clean_audit():
    virt = make_virt()
    san = PoolSanitizer(virt)
    san.audit()
    assert san.audits == 1


def test_san01_page_free_and_mapped():
    virt = make_virt()
    mapped = virt.requests[0].tables[0][0]
    virt.free_list.append(mapped)          # aliased: free AND mapped
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN01")


def test_san01_double_free():
    virt = make_virt()
    virt.free_list.append(virt.free_list[0])
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN01")


def test_san02_page_leak():
    virt = make_virt()
    virt.free_list.pop()                   # page conjured away
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN02")


def test_san03_refcount_drift():
    virt = make_virt()
    virt._refs[virt.requests[0].tables[0][0]] = 5
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN03")


def test_san04_swap_tier_drift():
    virt = make_virt()
    assert virt.swap_out(0) > 0
    virt.swapped_now += 1
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN04")


def test_san04_swap_slot_aliased_free_and_used():
    virt = make_virt()
    assert virt.swap_out(0) > 0
    _, _, slot = next(virt.requests[0].swapped_entries())
    virt.swap_free.append(slot)
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN04")


def test_san05_commit_outran_reservation():
    virt = make_virt()
    view = virt.views[MODEL]
    virt.requests[0].tokens += view.tokens_per_page * 4   # phantom commit
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN05")


def test_san05_ragged_layer_tables():
    virt = make_virt()
    tabs = virt.requests[0].tables
    if len(tabs) < 2:
        pytest.skip("model has a single KV layer")
    tabs[0].append(tabs[1].pop())          # pages conserved, tables ragged
    san = PoolSanitizer(virt)
    expect_rule(san, "SAN05")


def make_arena():
    arena = WeightArena(slab_bytes=4096)
    arena.views = {"m": SimpleNamespace(total_slabs=2, n_layers=1,
                                        slabs_per_layer=2)}
    arena.finalize(4, allocate=False)
    slabs = arena._take(2)
    arena.residency["m"] = Residency(
        slots=np.asarray(slabs, np.int32).reshape(1, 2),
        uploaded=np.zeros(1, bool), rev=1)
    return arena


def test_san06_unpin_before_finish():
    virt = make_virt()
    arena = make_arena()
    adm = SimpleNamespace(inflight={"m": 1})
    san = PoolSanitizer(virt, arena=arena, admission=adm)
    expect_rule(san, "SAN06")              # in flight, zero pins
    arena.pin("m")
    san.audit()                            # pinned -> clean


def test_san07_counter_bump_without_matching_hook():
    virt = make_virt()
    san = PoolSanitizer(virt)
    virt.hooks = san
    virt.swap_out_pages += 3               # drift injected behind the hook
    with pytest.raises(PoolSanitizerError) as ei:
        virt.swap_out(0)
    assert ei.value.rule == "SAN07"


def test_san08_arena_slab_aliased():
    virt = make_virt()
    arena = make_arena()
    san = PoolSanitizer(virt, arena=arena)
    san.audit()
    arena.free_list.append(int(arena.residency["m"].slots.ravel()[0]))
    expect_rule(san, "SAN08")


# ---------------------------------------------------------------------------
# engine integration: attached sanitizer is invisible in the streams
# ---------------------------------------------------------------------------

def run_engine(sanitize):
    import jax
    from repro.runtime.engine import CrossPoolEngine, EngineMode
    from repro.runtime.request import Request

    models = {MODEL: get_smoke_config(MODEL).replace(dtype="float32")}
    eng = CrossPoolEngine(
        models, page_budget=128, page_bytes=4096, slab_bytes=4096,
        max_batch=2, max_ctx=64,
        config=EngineConfig(mode=EngineMode(pipeline=True, lowering=True),
                            sanitize=sanitize),
        seed=0)
    streams = {}
    for i in range(3):
        req = Request(request_id=i, model=MODEL, prompt_tokens=4,
                      max_new_tokens=4, arrival_time=0.0)
        eng.submit(req, on_token=lambda e: streams.setdefault(
            e.request_id, []).append(e.token))
    eng.drain()
    return eng, streams


def test_sanitized_engine_streams_bit_exact(monkeypatch):
    # the CI sanitized leg exports CROSSPOOL_SANITIZE=1, which would
    # attach a sanitizer to the "off" engine too — clear it so this test
    # compares a genuinely detached engine against an attached one
    monkeypatch.delenv("CROSSPOOL_SANITIZE", raising=False)
    eng_off, streams_off = run_engine(False)
    eng_on, streams_on = run_engine(True)
    assert eng_off.sanitizer is None
    assert eng_on.sanitizer is not None
    assert streams_on == streams_off       # pure checking, zero behavior
    assert eng_on.sanitizer.audits > 0
    assert eng_on.sanitizer.events > 0


def test_env_var_attaches_sanitizer(monkeypatch):
    from repro.runtime.engine import CrossPoolEngine

    monkeypatch.setenv("CROSSPOOL_SANITIZE", "1")
    models = {MODEL: get_smoke_config(MODEL).replace(dtype="float32")}
    eng = CrossPoolEngine(models, page_budget=64, page_bytes=4096,
                          slab_bytes=4096, max_batch=1, max_ctx=32, seed=0)
    assert eng.sanitizer is not None
