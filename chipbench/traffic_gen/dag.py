"""The generator of dataflow-DAG traffic: a mix is nx, np, nt and how
many DAGs warm the programs up. DAGs run back to back, each from the
same seeded initial field; the seed makes the field and the sampled
points."""

from __future__ import annotations


class Dags:
    def __init__(self, traffic: dict, seed: int):
        self.nx, self.np_ = int(traffic["nx"]), int(traffic["np"])
        self.nt = int(traffic["nt"])
        self.warm_dags = int(traffic.get("warm_dags", 1))
        self.sample_points = int(traffic.get("sample_points", 1024))
        self.seed = int(seed)

    @property
    def total(self) -> int:
        return self.nx * self.np_


def make(traffic: dict, seed: int, **sizes) -> Dags:
    return Dags(traffic, seed)
