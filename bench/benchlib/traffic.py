"""The one generator of closed generation jobs.

A traffic file fixes a job: every (sampler, batch, cut) combination in
turn, in the file's order, until the job holds
``job_images_per_slot × slots`` images (the last request is cut to fit).
Clients, and on a configuration that takes class labels the labels, are
dealt round-robin over that order.  A sampler may carry ``"guidance": w``
(classifier-free guidance; its images take a cond and an uncond lane on
the server).  Every seed asks for the same job; the seed draws only the
weights, the keys and the check's sample.  Every request of a job arrives
at tick 0, and every job of a run repeats the composition with keys of
its own.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

from benchlib import spec


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request of the composition (its key is drawn per job)."""
    batch: int
    cut_ratio: float
    client: int
    sampler: str
    label: int = 0


def composition(traffic: dict, slots: int, clients: int,
                num_classes: int = 0) -> List[Spec]:
    cycle = [(name, b, c)
             for name in traffic["samplers"]
             for b in range(traffic["batch"]["min"],
                            traffic["batch"]["max"] + 1)
             for c in traffic["cut_ratios"]]
    n_images = traffic["job_images_per_slot"] * slots
    specs, total = [], 0
    while total < n_images:
        name, b, c = cycle[len(specs) % len(cycle)]
        b = min(b, n_images - total)
        i = len(specs)
        specs.append(Spec(batch=b, cut_ratio=c, client=i % clients,
                          sampler=name,
                          label=i % num_classes if num_classes else 0))
        total += b
    return specs


def job_images(specs: List[Spec]) -> int:
    return sum(s.batch for s in specs)


def lane_steps(config: dict, traffic: dict,
               specs: List[Spec]) -> Tuple[int, int]:
    """(server, client) lane-steps one job asks for: each image steps from
    the start of its trajectory to its cut on the server and from the cut
    to the end on its client, by the reference's trajectories and cuts.
    A guided image steps two server lanes (cond and uncond) and one
    client lane."""
    ref = spec.reference_module(config["reference"])
    T = config["schedule"]["T"]
    server = client = 0
    for s in specs:
        smp = traffic["samplers"][s.sampler]
        ts = ref.timesteps(T, smp["family"], smp.get("num_steps", 0))
        cut = ref.cut_position(ts, T, s.cut_ratio)
        lanes = 2 if smp.get("guidance") is not None else 1
        server += s.batch * cut * lanes
        client += s.batch * (len(ts) - cut)
    return server, client
