"""A run with the timed path broken underneath comes out not correct.

The harness runs at smoke widths on the CPU with its chip check skipped.
The fault alters every token where the fused decode step produces it
(the next id in the vocabulary), so the served tokens stop being the
model's first choices and the comparison must say so.
"""
import jax.numpy as jnp

import smoke


def _alter_tokens(engine):
    for runner in engine.runners.values():
        fused = runner.fused
        vocab = runner.cfg.vocab_size
        real = fused.__call__

        def altered(*a, _real=real, _vocab=vocab, **kw):
            toks, pool = _real(*a, **kw)
            return jnp.where(toks >= 0, (toks + 1) % _vocab, toks), pool

        runner.fused = altered


def test_sound_run_is_correct(tmp_path):
    out = smoke.run(tmp_path, "smoke.decode", seconds=1.5)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"moonshot_mean_gap", "minicpm3_widest_gap"}
    assert list(out)[-1] == "checks"


def test_altered_tokens_are_caught(tmp_path):
    out = smoke.run(tmp_path, "smoke.decode", seconds=1.5,
                    fault=_alter_tokens)
    assert not out["correct"]
    assert all(c["value"] > c["limit"] for c in out["checks"].values())
