"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the
tests that drive a whole run with the chip check skipped."""
import time

from perfbench import harness, spec

TINY = {
    "lm": {"hidden_size": 32, "intermediate_size": 64,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_hidden_layers": 1, "vocab_size": 128},
}
TINY_TRAFFIC = {"lm": {"seq": 8}}
ONE_CHIP = [w["name"] for w in spec.load_benchmark()["workloads"]
            if w["chips"] == 1]


def tiny(cell: str) -> dict:
    """A cell of BENCHMARK.json at a tiny size."""
    res = spec.resolve(spec.load_benchmark(), cell)
    system = res["config"]["system"]
    res["config"].update(TINY[system])
    res["traffic"].update(TINY_TRAFFIC[system])
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
      (the first half of every sequence), the mean taken over them;
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
