"""Floating-point operations the algorithm needs, counted from shapes.

This is the numerator of the model-FLOP utilization metric. It counts what the
mathematics of a round requires, never what a particular lowering executes
(XLA's cost analysis of the compiled round is a different number: the repo's
"27.2 TFLOP per round" came from there) and never recomputed or discarded
work:

- a layer is a multiply-accumulate count ``macs`` of its forward pass;
  forward = 2 x macs, weight gradient = 2 x macs, input gradient = 2 x macs,
  and the network's first layer needs no input gradient;
- a local step needs the gradient of a minibatch of ``batch_size`` draws
  with replacement from ``n_local`` samples, which has at most
  ``min(batch_size, n_local)`` distinct samples: that many forward/backward
  passes. (The engine's multiplicity mode runs all ``n_local`` samples with
  weights, the gather mode runs ``batch_size`` rows; both are at least this
  count, so a utilization built on it cannot pass 100% by over-counting.)
- every resident client is computed every round (the resident program
  zeroes the weight of a withheld client, it does not skip it), so the
  needed work follows the resident population, not the participants;
- evaluation is ``eval_n`` forward passes.

The per-sample layer lists live with each model's reference
(``benchmark/reference/<model>.py``: ``layers(model)``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List


@dataclasses.dataclass(frozen=True)
class Layer:
    """One matmul-like layer of a forward pass over ONE sample."""

    name: str
    macs: float                 # multiply-accumulates, forward, one sample
    input_grad: bool = True     # False for the first layer of the network


def dense(name: str, rows: int, fan_in: int, fan_out: int,
          input_grad: bool = True) -> Layer:
    """``rows`` positions (1 for a pooled vector, L for a token sequence)."""
    return Layer(name, float(rows * fan_in * fan_out), input_grad)


def matmul(name: str, m: int, k: int, n: int) -> Layer:
    """Activation x activation product (attention scores, scores x values):
    both operands need a gradient, which is the same 2 x macs each as a
    weight layer's two backward products."""
    return Layer(name, float(m * k * n), True)


def forward_flops(layers: Iterable[Layer]) -> float:
    return sum(2.0 * layer.macs for layer in layers)


def train_flops(layers: Iterable[Layer]) -> float:
    """Forward + weight-gradient + input-gradient passes of one sample."""
    return sum(2.0 * layer.macs * (3.0 if layer.input_grad else 2.0)
               for layer in layers)


def samples_per_step(batch_size: int, n_local: int) -> int:
    return min(int(batch_size), int(n_local))


def round_flops(layers: List[Layer], *, clients: int, local_steps: int,
                batch_size: int, n_local: int, eval_n: int = 0
                ) -> Dict[str, float]:
    """FLOPs one round of the cell needs: every resident client's local
    steps, plus ``eval_n`` forward passes where the round evaluates."""
    train_samples = clients * local_steps * samples_per_step(batch_size, n_local)
    train = train_samples * train_flops(layers)
    evaluate = eval_n * forward_flops(layers)
    return {"train_samples": float(train_samples), "train": train,
            "evaluate": evaluate, "total": train + evaluate}


def cell_round_flops(layers: List[Layer], params: Dict[str, Any],
                     clients: int, evaluates: bool) -> Dict[str, float]:
    """``round_flops`` with the sizes read from a composed task's engine
    params (``manifest.engine_params``)."""
    fed = params["fedcore"]
    data = params.get("data", {})
    return round_flops(
        layers, clients=clients, local_steps=int(fed["max_local_steps"]),
        batch_size=int(fed["batch_size"]),
        n_local=int(data["synthetic"]["n_local"]),
        eval_n=int(data.get("eval_n") or 0) if evaluates else 0,
    )
