"""How ``correct`` is decided: one masked check round through the program's
own compiled round program, against the plain float32 reference.

After the window has closed and the task is STOPPED, on the finished runner:

1. Take the placed dataset and the server state as the window left them
   (``runner.states``): the global parameters after the window's rounds and
   the server optimizer's memory — for FedAdam the first and second moments
   and the step count the window built up — so the server step under test
   is an Adam step WITH memory (its bias corrections, b1 and b2 all enter
   the result; from a fresh state the first Adam step does not depend on
   b2 at all). Draw ``check_clients`` real clients from the seed, always
   including the first and the last resident client (both ends of the block
   scan, and on four chips the first and the last chip).
2. Run ONE more round through ``runner.core.round_step`` — the same jitted
   function, shapes and executable the window drove — with a ``participate``
   mask that admits only those clients. The mask is data: nothing retraces,
   every resident client is still computed, only the sampled ones enter the
   aggregate.
3. Independently compute the same round from the same starting state with
   ``benchmark/reference/fedround.py`` + the configuration's model
   reference + its server step, in float32.
4. Compare, each number against a limit of its own from the configuration's
   file (``check.limits``; PERF.md gives the readings each was set from):

   - ``clients_trained``: the round's count against the sample size (exact);
   - ``client_loss_gap``: worst sampled client, |program - reference| over
     the reference's loss;
   - ``pseudo_grad_global_rel_l2``: the aggregate the server optimizer gets
     (the weighted-mean client delta; for a stateful server optimizer
     recovered from its state after the step), all leaves as one vector,
     ||program - reference|| over ||reference||: steady from seed to seed,
     so it carries the tight limit;
   - ``pseudo_grad_rel_l2``: the same by the worst leaf, over the
     reference's norm of that leaf or of the median leaf, whichever is
     larger (some leaves' deltas are all but zero). A widest gap: it swings
     from seed to seed, so its limit is loose;
   - ``param_delta_global_rel_l2`` / ``param_delta_rel_l2``: the same two on
     the change of the global parameters (new - old), which is what the
     server step makes of it (an unchanged state reads exactly 1);
   - ``pseudo_grad_norm_gap`` / ``param_delta_norm_gap``: worst leaf
     | ||program|| - ||reference|| | over the same denominator (steadier
     than the difference's norm where bfloat16 inputs make the elementwise
     difference noisy).

   **Which leaves count in the four worst-leaf numbers:** those of more
   than ``FEW_ELEMENTS`` elements. A leaf of a few elements has nothing to
   average over. DistilBERT's 2-class head bias moves as (u, -u), one
   number. Its gradient is a sum of per-sample ``p - y`` with heavy
   cancellation: where a sampled client's local steps amplify a rounding
   (the float32 reference itself, fed bfloat16 matmul inputs, drifts as far
   as the program does) and the four clients' deltas all but cancel, that
   one number read 0.635 of the median leaf's norm where the worst of the
   101 other leaves read 0.264 (the driver's seed 1955436291, PR 46). And
   under a server optimizer with memory its change is the small remainder
   of the carried first moment and the check round's gradient (0.072 where
   the worst of the others read 0.0016, PR 32). Under the lower-precision
   control the leaf reads 0.29-1.88 (its change 0.020-1.5), inside the
   sound runs' range: it separates nothing.
   Such a leaf stays in both whole-vector numbers and is printed every run
   in the check's detail as ``pseudo_grad_few_elements_gap`` and
   ``param_delta_few_elements_gap``, which no limit judges. The rule is on
   the reference's shapes, not on a name; of the benchmark's models only
   DistilBERT has such a leaf (the decoders' smallest have 32 and 64
   elements). PERF.md section 2 has both looks.

   The deltas, not the parameters, are compared: parameters barely move in
   one round, so any comparison of them passes whatever the round did.

The check round takes every local step of the configuration
(``fedcore.max_local_steps``, the ``num_steps`` the window's rounds ran
with), so steps after the first — a new minibatch from the carried
parameters — are compared too. Local SGD at this configuration's rate is
touchy: the bfloat16-input program and the float32 reference can drift
apart over the steps on a client whose loss is high (one sound check in 54
read a client's mean loss 10% off and the aggregate 6% off, the others under
3%), which is why the whole-vector limits sit at about three times that
reading and not at three times the typical one.

The limits admit the configuration's stated precision (bfloat16 matmul/conv
inputs, float32 everything else) and reject the program's own
lower-precision path, ``fedcore.carry_dtype: "bf16"`` (``control.py``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class CheckResult:
    correct: bool
    numbers: Dict[str, float]
    limits: Dict[str, float]
    sample: List[int]
    seconds: float = 0.0
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def lines(self) -> List[str]:
        out = []
        for name, value in self.numbers.items():
            limit = self.limits.get(name)
            verdict = ("not judged" if limit is None
                       else "ok" if value <= limit else "OVER")
            out.append(f"check {name}={value:.6g} limit={limit} {verdict}")
        out.append(f"check detail {self.detail}")
        return out


def sample_clients(num_real: int, k: int, seed: int) -> List[int]:
    """``k`` distinct real clients from the seed, the first and last always
    among them."""
    k = min(k, num_real)
    fixed = [0, num_real - 1] if num_real > 1 and k >= 2 else [0][:k]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [int(c) for c in rng.permutation(num_real) if c not in fixed]
    return sorted(fixed + rest[: k - len(fixed)])


def flatten(tree) -> Dict[str, np.ndarray]:
    """A parameter tree as ``{"A/B/kernel": float32 host array}``."""
    import jax

    out = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        name = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                        for p in path)
        out[name] = np.asarray(jax.device_get(leaf), np.float32)
    return out


def adam_state(opt_state) -> Optional[Dict[str, Any]]:
    """``{"m", "v", "count"}`` of an optax Adam-family state (flat, host), or
    None for a stateless server optimizer."""
    import jax

    found: Dict[str, Any] = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    for path, leaf in leaves:
        attrs = [getattr(p, "name", None) for p in path]
        for field, slot in (("mu", "m"), ("nu", "v")):
            if field in attrs:
                tail = path[attrs.index(field) + 1:]
                name = "/".join(str(getattr(p, "key", p)) for p in tail)
                found.setdefault(slot, {})[name] = np.asarray(
                    jax.device_get(leaf), np.float32)
        if "count" in attrs and "count" not in found:
            found["count"] = int(jax.device_get(leaf))
    return found if "m" in found else None


FEW_ELEMENTS = 8


def worst_leaf(program: Dict[str, np.ndarray], reference: Dict[str, np.ndarray],
               more_than: int = 0) -> Dict[str, float]:
    """Worst-leaf relative L2 error and norm gap of ``program`` against
    ``reference`` (see the module docstring for the denominator), over the
    leaves of more than ``more_than`` elements; the others' worst norm gap
    comes back apart as ``few_elements_gap``, and every leaf counts in
    ``global_rel_l2`` and in the median that floors the denominator."""
    ref_norm = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
                for k, v in reference.items()}
    floor = float(np.median(list(ref_norm.values())))
    rel, gap, few_gap, where = 0.0, 0.0, 0.0, ""
    err2 = ref2 = 0.0
    for k, r in reference.items():
        denom = max(ref_norm[k], floor, 1e-30)
        p = np.asarray(program[k], np.float64)
        err = float(np.linalg.norm(p - np.asarray(r, np.float64)))
        err2 += err * err
        ref2 += ref_norm[k] ** 2
        e = err / denom
        g = abs(float(np.linalg.norm(p)) - ref_norm[k]) / denom
        if not math.isfinite(e):
            e = g = math.inf
        if np.size(r) <= more_than:
            few_gap = max(few_gap, g)
            continue
        if e > rel:
            rel, where = e, k
        gap = max(gap, g)
    whole = math.sqrt(err2 / max(ref2, 1e-60))
    return {"rel_l2": rel, "norm_gap": gap, "leaf": where,
            "few_elements_gap": few_gap,
            "global_rel_l2": whole if math.isfinite(whole) else math.inf}


def perturbed_references(server, algorithm: Dict[str, Any],
                         ref: Dict[str, Any], opt0: Optional[Dict[str, Any]],
                         program_delta: Dict[str, np.ndarray]
                         ) -> Dict[str, Dict[str, float]]:
    """What the parameter-delta numbers read when the server step is wrong:
    the program's delta against the reference's step redone with one fault
    planted in it (the gap is the one a program with that fault would show
    against the sound reference). Only for a stateful server optimizer; for
    ``control.py``'s readings, never part of a benchmark run. (The planted
    local-step fault, a last step left out, is read in ``run_check``: it
    needs a second pass of the reference.)"""
    if opt0 is None:
        return {}
    zeros = {k: np.zeros_like(v) for k, v in opt0["m"].items()}
    plants = {
        "b2_0.999": (dict(algorithm, b2=0.999), opt0),
        "b1_0.8": (dict(algorithm, b1=0.8), opt0),
        "memory_dropped": (algorithm, {"m": zeros, "v": zeros, "count": 0}),
        "count_not_advanced": (
            algorithm, dict(opt0, count=max(int(opt0["count"]) - 1, 0))),
    }
    out = {}
    for name, (alg, opt) in plants.items():
        update, _ = server.step(ref["mean_delta"], opt, alg)
        w = worst_leaf(program_delta, update, FEW_ELEMENTS)
        out[name] = {"param_delta_global_rel_l2": w["global_rel_l2"],
                     "param_delta_rel_l2": w["rel_l2"],
                     "param_delta_norm_gap": w["norm_gap"]}
    return out


def run_check(runner, cell, task: Dict[str, Any], seed: int,
              plant: bool = False) -> CheckResult:
    """The check round on a finished ``runner`` (see the module docstring).
    ``seed`` picks the sampled clients. The round's result becomes the
    runner's state for that population, so a second check starts where the
    first ended. ``plant`` adds what the numbers read against a reference
    with a fault planted in it to the detail (``control.py``'s readings
    only: it doubles the reference's time)."""
    import time

    import jax

    from benchmark import manifest
    from olearning_sim_tpu.parallel.mesh import global_put

    t0 = time.perf_counter()
    config = cell.config
    params = manifest.engine_params(task)
    fed = params["fedcore"]
    model = manifest.find_module("reference", config["reference"],
                                 cell.files_root)
    server = manifest.find_module(
        "reference", "server_" + config["algorithm"]["name"], cell.files_root)
    limits = dict(config.get("check", {}).get("limits", {}))
    steps = int(fed["max_local_steps"])

    population = runner.populations[0]
    ds, core = population.dataset, runner.core
    state = runner.states[population.name]
    sample = sample_clients(ds.num_real_clients,
                            int(cell.traffic["check_clients"]), seed)

    # Inputs of the round, read before the program consumes (donates) them.
    params0 = flatten(state.params)
    opt0 = adam_state(state.opt_state)
    round_idx = int(jax.device_get(state.round_idx))
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key)))
    rows = np.asarray(sample)
    xs = np.asarray(jax.device_get(ds.x[rows]))
    if not np.issubdtype(xs.dtype, np.integer):
        xs = xs.astype(np.float32)      # stored bfloat16: exact in float32
    ys = np.asarray(jax.device_get(ds.y[rows]), np.int64)
    num_samples = np.asarray(jax.device_get(ds.num_samples))
    uids = np.asarray(jax.device_get(ds.client_uid))
    weights = np.asarray(jax.device_get(ds.weight))
    clients = [{"x": xs[j], "y": ys[j], "num_samples": int(num_samples[c]),
                "uid": int(uids[c]), "weight": float(weights[c])}
               for j, c in enumerate(sample)]

    # The program's round, through the window's own compiled function.
    mask = np.zeros(ds.num_clients, np.float32)
    mask[rows] = 1.0
    participate = global_put(mask, core.plan.client_sharding())
    num_steps = global_put(np.full(ds.num_clients, steps, np.int32),
                           core.plan.client_sharding())
    new_state, metrics = core.round_step(state, ds, participate=participate,
                                         num_steps=num_steps)
    del state
    runner.states[population.name] = new_state
    program_loss = np.asarray(jax.device_get(metrics.client_loss))[rows]
    trained = int(jax.device_get(metrics.clients_trained))
    params1 = flatten(new_state.params)
    program_delta = {k: params1[k] - params0[k] for k in params0}
    opt1 = adam_state(new_state.opt_state)
    program_grad = server.recover_mean_delta(opt0, opt1, config["algorithm"])
    if program_grad is None:
        program_grad = program_delta

    # The reference's round from the same inputs.
    from benchmark.reference import fedround

    ref = fedround.reference_round(
        model, server, config["algorithm"], params0, opt0, clients, base_key,
        round_idx, steps=steps, batch_size=int(fed["batch_size"]))

    ref_loss = np.asarray(ref["client_loss"], np.float64)
    loss_gap = float(np.max(
        np.abs(program_loss.astype(np.float64) - ref_loss)
        / np.maximum(np.abs(ref_loss), 1e-6)))
    grad = worst_leaf(program_grad, ref["mean_delta"], FEW_ELEMENTS)
    delta = worst_leaf(program_delta, ref["param_delta"], FEW_ELEMENTS)
    numbers = {
        "clients_trained_gap": float(abs(trained - len(sample))),
        "client_loss_gap": loss_gap if math.isfinite(loss_gap) else math.inf,
        "pseudo_grad_global_rel_l2": grad["global_rel_l2"],
        "param_delta_global_rel_l2": delta["global_rel_l2"],
        "pseudo_grad_rel_l2": grad["rel_l2"],
        "pseudo_grad_norm_gap": grad["norm_gap"],
        "param_delta_rel_l2": delta["rel_l2"],
        "param_delta_norm_gap": delta["norm_gap"],
    }
    correct = all(numbers[k] <= v for k, v in limits.items()) and bool(limits)
    detail = {"local_steps": steps, "round_idx": round_idx,
              "server_count": None if opt0 is None else opt0["count"],
              "worst_leaf": {"pseudo_grad": grad["leaf"],
                             "param_delta": delta["leaf"]},
              "pseudo_grad_few_elements_gap": grad["few_elements_gap"],
              "param_delta_few_elements_gap": delta["few_elements_gap"],
              "client_loss": [[round(float(p), 5), round(float(r), 5)]
                              for p, r in zip(program_loss, ref_loss)]}
    if plant:
        detail["planted"] = perturbed_references(
            server, config["algorithm"], ref, opt0, program_delta)
        short = fedround.reference_round(
            model, server, config["algorithm"], params0, opt0, clients,
            base_key, round_idx, steps=steps - 1,
            batch_size=int(fed["batch_size"]))
        w = worst_leaf(program_grad, short["mean_delta"], FEW_ELEMENTS)
        detail["planted"]["last_step_dropped"] = {
            "pseudo_grad_global_rel_l2": w["global_rel_l2"],
            "pseudo_grad_rel_l2": w["rel_l2"],
            "pseudo_grad_norm_gap": w["norm_gap"]}
    return CheckResult(correct=correct, numbers=numbers, limits=limits,
                       sample=sample, seconds=time.perf_counter() - t0,
                       detail=detail)
