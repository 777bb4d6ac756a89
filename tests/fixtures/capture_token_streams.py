"""Re-draw ``pre_refactor_token_streams.json`` with the installed JAX.

The streams depend on the random weights, so they change when JAX changes
its PRNG (e.g. the ``jax_threefry_partitionable`` default).  Run from the
repository root, on the CPU:

    PYTHONPATH=src:tests JAX_PLATFORMS=cpu python tests/fixtures/capture_token_streams.py
"""
import json
import os

import jax

from test_session import _engine, _streams, _trace_fused, _trace_host

PATH = os.path.join(os.path.dirname(__file__),
                    "pre_refactor_token_streams.json")


def main() -> None:
    out = {"drawn_with": {
        "jax": jax.__version__,
        "jax_threefry_partitionable": bool(
            jax.config.jax_threefry_partitionable)}}
    for key, lowering, mk in (("fused_pipeline", True, _trace_fused),
                              ("host_pipeline", False, _trace_host)):
        reqs = mk()
        stats = _engine(lowering=lowering).run(reqs)
        out[key] = {"streams": _streams(reqs), "tokens_out": stats.tokens_out}
    with open(PATH, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    main()
