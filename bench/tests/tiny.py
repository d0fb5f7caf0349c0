"""A tiny cell for tests on the CPU: the harness's path at toy sizes."""
import json
import os

from harness import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# The comparison's limits at this size, set as the configurations' are
# but from CPU readings here: the program reads at most 1.5e-4, 0.0086,
# 0.056 and 0.0036, the float8 control at least 4.4e-4, 0.011, 0.14 and
# 0.0078, and half a batch 0.38 on the gradient norm.
TINY_LIMITS = {"loss_gap": 3e-4, "grad_norm_gap": 0.03, "grad_diff_gap": 0.1,
               "leaf_change_gap": 0.006}


def tiny_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["limits"] = dict(TINY_LIMITS)
    m = cfg["model"]
    m.update(n_layers=4, d_model=64, vocab_size=256)
    m["ssm"].update(state_dim=16, head_dim=16, chunk_size=32)
    m.update(vocab_chunk=128, remat_group=2)
    return cfg


def tiny_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        tr = json.load(f)
    tr.update(batch=2, seq=64, corpus_tokens=16384, doc_tokens_mean=48)
    if tr["checkpoint_every"]:
        tr["checkpoint_every"] = 8
    return tr


def tiny_cell(config: str = "mamba2-370m", traffic: str = "steady",
              name: str = "tiny") -> spec.Cell:
    """The cell ``<config>.<traffic>`` at a tiny size, with the metrics
    that cell reports in ``BENCHMARK.json`` (for a cell that is not there,
    those every cell reports)."""
    bench = spec.load_benchmark()
    return spec.Cell(name=name, chips=1, config=tiny_config(config),
                     config_name=config, traffic=tiny_traffic(traffic),
                     traffic_name=traffic,
                     metrics=spec._metrics(bench, f"{config}.{traffic}"))
