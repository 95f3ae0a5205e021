"""Whether the timed path's outputs are correct: a sample of the finished
requests, drawn from the seed, replayed alone by the configuration's
plain reference (``bench/reference/<name>.py``), and the gaps of x_c and
x_0 held against the cell's limits (``bench/checks/<cell>.json``)."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import numpy as np

from benchlib import spec
from benchlib.cell import weight_keys


def draw_sample(jobs: List[Dict[int, dict]], seed: int) -> List[dict]:
    """From one job drawn from the seed: one request, drawn from the seed,
    of every (sampler, cut, client) group the job holds, so that every
    client model and the longest server and client segments are in the
    sample."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    outputs = jobs[int(rng.integers(len(jobs)))]
    groups: Dict[tuple, List[int]] = {}
    for rid in sorted(outputs):
        r = outputs[rid]["request"]
        groups.setdefault((r.sampler, r.cut_ratio, r.client_idx),
                          []).append(rid)
    return [outputs[rids[int(rng.integers(len(rids)))]]
            for _, rids in sorted(groups.items())]


def reference_outputs(config: dict, traffic: dict, sample: List[dict],
                      seed: int, precision: str,
                      weights_dtype=None) -> List[tuple]:
    """(x_c, x_0) of every image of the sampled requests, from the plain
    reference at ``precision``; ``weights_dtype`` rounds its weights
    through that type first (a lower-precision control where the backend
    ignores the matmul precision, as the CPU does).  On a configuration
    that takes class labels, each lane is replayed with its request's
    label and its sampler's guidance weight (None: unguided)."""
    ref = spec.reference_module(config["reference"])
    m = config["model"]
    conditional = spec.family(config).num_classes(m) > 0
    sch = ref.schedule(config["schedule"]["name"], config["schedule"]["T"])
    T = config["schedule"]["T"]
    k_server, k_clients = weight_keys(seed, config["engine"]["clients"])
    init = jax.jit(functools.partial(_init, ref.init_params, m=m,
                                     dtype=weights_dtype))
    clip = config["engine"]["clip"]
    out = []
    with jax.default_matmul_precision(precision):
        server = init(k_server)
        clients = {}
        for item in sample:
            r = item["request"]
            s = traffic["samplers"][r.sampler]
            ts = ref.timesteps(T, s["family"], s.get("num_steps", 0))
            coefs = ref.coefficients(sch, ts, s["family"], s.get("eta", 1.0))
            cut = ref.cut_position(ts, T, r.cut_ratio)
            if r.client_idx not in clients:
                clients[r.client_idx] = init(k_clients[r.client_idx])
            cond = ({"label": r.label, "guidance": s.get("guidance")}
                    if conditional else {})
            for i in range(r.batch):
                out.append(ref.replay_lane(server, clients[r.client_idx],
                                           r.key, i, m, ts, coefs, cut,
                                           clip, **cond))
    return out


def _init(init_params, key, m, dtype):
    params = init_params(key, m)
    if dtype is None:
        return params
    return jax.tree.map(lambda a: a.astype(dtype).astype(a.dtype), params)


def gaps(sample: List[dict], ref_out: List[tuple]) -> Dict[str, float]:
    """The compared numbers: widest and mean |gap| of x_c and of x_0."""
    got_c = np.stack([x for item in sample for x in item["x_c"]])
    got_0 = np.stack([x for item in sample for x in item["x_0"]])
    ref_c = np.stack([c for c, _ in ref_out])
    ref_0 = np.stack([z for _, z in ref_out])
    dc, d0 = np.abs(got_c - ref_c), np.abs(got_0 - ref_0)
    nan = float("inf")
    finite = np.isfinite(dc).all() and np.isfinite(d0).all()
    return {"xc_max_abs": float(dc.max()) if finite else nan,
            "xc_mean_abs": float(dc.mean()) if finite else nan,
            "x0_max_abs": float(d0.max()) if finite else nan,
            "x0_mean_abs": float(d0.mean()) if finite else nan}


def in_place_of_program(sample: List[dict],
                        outputs: List[tuple]) -> List[dict]:
    """The sample with its x_c and x_0 replaced by ``outputs`` (one
    (x_c, x_0) per image, in sample order): a control put in the
    program's place."""
    served, i = [], 0
    for item in sample:
        n = item["request"].batch
        served.append(dict(item, x_c=[c for c, _ in outputs[i:i + n]],
                           x_0=[z for _, z in outputs[i:i + n]]))
        i += n
    return served


def judge(readings: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each compared number beside its limit."""
    return {name: {"value": readings[name],
                   "limit": limits["numbers"][name]["limit"]}
            for name in limits["numbers"]}


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
