"""Decide ``correct``: served tokens against the plain reference.

After the window, a sample of finished requests per model, drawn from the
seed and always holding the longest, goes through the model's plain
reference (the module its entry names under ``reference``, default
``cb.reference``) once each (prompt plus served tokens, teacher-forced).
Each served token's gap is how far its reference logit lies below the
reference's best at its position, in units of that position's logit
standard deviation.  Every token is greedy, so a served token is the
program's own first choice there.  A model's ``checks`` (configuration
file) name the numbers taken over those gaps (:func:`statistic`) and the
limit of each; :func:`judge` decides ``correct`` from them.

A control (one of the reference module's ``QUANTIZERS``) is read at the
same positions: the gap of the token the lower-precision reference puts
first there.  Judged by the same :func:`judge` in the program's place, it
has to come out as not correct.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Sequence, Tuple

import numpy as np


def sample_requests(reqs, models, per_model: int, seed: int
                    ) -> Dict[str, List]:
    """Per model: the longest finished request, then others drawn from the
    seed; where fewer finished, the longest served prefixes of requests
    still in flight make up the count."""
    rng = np.random.default_rng([seed, 5])
    out = {}
    for m in models:
        mine = [r for r in reqs if r.model == m and r.phase != "warmup"
                and not r.rejected and len(r.tokens) >= 2]
        done = sorted((r for r in mine if r.done),
                      key=lambda r: (-len(r.tokens), r.rid))
        pick = done[:1]
        rest = done[1:]
        if rest:
            k = min(per_model - 1, len(rest))
            pick += [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                        replace=False))]
        if len(pick) < per_model:
            live = sorted((r for r in mine if not r.done),
                          key=lambda r: (-len(r.tokens), r.rid))
            pick += live[: per_model - len(pick)]
        out[m] = pick
    return out


def statistic(kind: str, gaps: List[np.ndarray]) -> float:
    """One number over every compared token of a model's sample:
    ``widest`` (the largest gap) or ``mean``."""
    g = np.concatenate(gaps)
    if kind == "widest":
        return float(g.max())
    if kind == "mean":
        return float(g.mean())
    raise ValueError(f"unknown check statistic {kind!r}")


def compare(cfg: Dict, repo_models, samples: Dict[str, List], seed: int,
            controls: Sequence[str] = ()) -> Dict[str, Dict]:
    """Per model: the gap of every served token of every sampled request,
    and under ``controls[mode]`` the gap of each control's own first choice
    at the same positions."""
    import jax
    from cb.setup import model_keys, reference_module, seeded_params
    keys = model_keys(cfg, seed)
    max_ctx = int(cfg["engine"]["max_ctx"])
    out = {}
    for name, reqs in samples.items():
        if not reqs:
            continue
        mod = reference_module(cfg["models"][name])
        unknown = set(controls) - set(mod.QUANTIZERS)
        if unknown:
            raise ValueError(f"{name}: {mod.__name__} has no control "
                             f"{sorted(unknown)}")
        m = cfg["models"][name]["as_run"]
        params = seeded_params(repo_models[name], keys[name],
                               jax.devices()[0])
        ref = mod.Reference(m, max_ctx, max_ctx, "f32")
        ctls = [mod.Reference(m, max_ctx, max_ctx, c) for c in controls]
        rd = out[name] = {"requests": len(reqs), "token_gaps": [],
                          "controls": {c: [] for c in controls}}
        for r in reqs:
            g = mod.served_gaps(ref, params, r.prompt, r.tokens, ctls)
            rd["token_gaps"].append(g[:, 0])
            for j, c in enumerate(controls):
                rd["controls"][c].append(g[:, 1 + j])
        del params, ref, ctls
        gc.collect()
    return out


def judge(cfg: Dict, gaps: Dict[str, List[np.ndarray]]
          ) -> Tuple[Dict[str, Dict], bool]:
    """(every number compared with its limit, ``correct``) for the gaps of
    each model: correct when every model was compared and each number is
    within its limit."""
    checks, correct = {}, set(gaps) == set(cfg["models"])
    for name, g in gaps.items():
        for c in cfg["models"][name]["checks"]:
            v = statistic(c["statistic"], g)
            checks[c["name"]] = {"value": v, "limit": c["limit"]}
            correct &= v <= c["limit"]
    return checks, bool(correct)
