"""The disaggregated layout (``colo2-disagg4``) at smoke widths on four
virtual CPU devices: KV pool and attention on the first, the weights arena
and the FFN on the last, hidden states crossing each layer.  A sound run is
correct; with the exchange between the chips left out (the hidden state
that crosses arrives as zeros) it is not.

Four devices need their own process: the flag that gives the CPU backend
four is read when JAX starts.
"""
import json
import os
import subprocess
import sys

import smoke

SCRIPT = r"""
import copy, json, sys, tempfile
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import smoke
import jax
import jax.numpy as jnp
import repro.core.control as control
import repro.core.pipeline as pipeline

smoke.CONFIG = copy.deepcopy(smoke.CONFIG)
smoke.CONFIG["engine"]["lowering"] = False


def leave_out_exchange(engine):
    def lost(x, device):
        return jnp.zeros_like(jax.device_put(x, device))
    control.transfer = pipeline.transfer = lost


def placement(engine):
    arena = engine.w_pool.arena
    return [engine.kv_device.id, engine.w_device.id, arena.device.id]


out = {}
for name, fault in (("sound", None), ("exchange_left_out",
                                       leave_out_exchange)):
    seen = {}

    def hook(engine, fault=fault):
        seen["placement"] = placement(engine)
        if fault is not None:
            fault(engine)
    with tempfile.TemporaryDirectory() as d:
        r = smoke.run(Path(d), "smoke.decode", seconds=1.5, seed=2**31 + 9,
                      fault=hook)
    out[name] = {"correct": r["correct"], "checks": r["checks"],
                 "count": r["device"]["count"], **seen}
print(json.dumps(out))
"""


def test_disaggregated_run_is_correct_and_its_exchange_is_checked():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(smoke.HERE)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    sound, broken = out["sound"], out["exchange_left_out"]
    assert sound["count"] == 4
    assert sound["placement"] == [0, 3, 3]
    assert sound["correct"], sound["checks"]
    assert not broken["correct"], broken["checks"]
