"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
tests that drive a whole run with the chip check skipped. Each system gives
its own tiny sizes (``TINY``, ``TINY_TRAFFIC``)."""
import importlib
import json
import os
import time

from perfbench import harness, spec

ONE_CHIP = [w["name"] for w in spec.load_benchmark()["workloads"]
            if w["chips"] == 1]


def tiny(cell: str, traffic: str | None = None) -> dict:
    """A cell of BENCHMARK.json at its system's tiny size; ``traffic``
    names another mix of ``traffic/`` to run its configuration under (one
    kept for a cell that BENCHMARK.json does not hold yet)."""
    res = spec.resolve(spec.load_benchmark(), cell)
    if traffic is not None:
        with open(os.path.join(spec.HERE, "traffic", f"{traffic}.json")) as f:
            res["traffic"] = json.load(f)
    system = importlib.import_module(
        f"perfbench.systems.{res['config']['system']}")
    res["config"].update(system.TINY)
    res["traffic"].update(system.TINY_TRAFFIC)
    return res


def run(res: dict, seed: int = 2 ** 33 + 7, seconds: float = 0.1) -> dict:
    return harness.run_cell(res, seed=seed, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), cache=False)


def plant(fault: str, patch) -> None:
    """Break the timed path underneath the harness. ``patch(obj, name,
    value)`` sets an attribute for the test's duration (pytest's
    ``monkeypatch.setattr``).

    * ``unchanged`` — a local step returns its state as it was;
    * ``half_batch`` — the model's loss reads half of each batch's tokens
      (the first half of every sequence), the mean taken over them: it
      patches ``repro.models.transformer.loss_fn``, which every LM system's
      oracle calls through the module;
    * ``no_exchange`` — the sync delivers nothing: every worker keeps its
      own anchor (on a mesh, the exchange between chips left out).
    """
    from repro.core.worker import AdaSEGWorker
    from repro.models import transformer

    if fault == "unchanged":
        patch(AdaSEGWorker, "step",
              lambda self, problem, state, rng, *, enabled=None: state)
    elif fault == "half_batch":
        loss_fn = transformer.loss_fn

        def half(params, cfg, batch):
            cut = batch["tokens"].shape[1] // 2
            return loss_fn(params, cfg,
                           {k: v[:, :cut] for k, v in batch.items()})

        patch(transformer, "loss_fn", half)
    elif fault == "no_exchange":
        patch(AdaSEGWorker, "merge_synced", lambda self, state, payload: state)
    else:
        raise ValueError(fault)
