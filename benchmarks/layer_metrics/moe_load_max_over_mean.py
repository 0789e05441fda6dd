"""Experts: the most loaded held expert's tokens over the mean held expert's,
from the program's counters (`moe.expert_load_max`: a step's largest group over
all routed layers; `moe.expert_load_mean`: assignments to held experts over
held experts and layers), means over the run's steps.  1 is perfect balance;
the grouped matmul's time follows the sum, its tail the largest."""

from benchmarks import moe_reduce


def read(run: dict) -> float | None:
    counted = moe_reduce.routing(run)
    if not counted or not counted["moe.expert_load_mean"]:
        return None
    return counted["moe.expert_load_max"] / counted["moe.expert_load_mean"]
