"""A cell at CPU size, driven through everything a run does after its look
for chips (``run.execute``), for the harness's tests."""
import time
from pathlib import Path

from bench import spec

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell(kind: str) -> dict:
    """The tiny model under a tiny mix, held to limits set from the tiny
    cell's own CPU readings (``data/tiny-*-limits.json``), since its gaps,
    losses and gradients differ from the real cells'."""
    if kind == "serve":
        conf, traffic, cell = "tiny-moe", "tiny-chat", "olmoe-chat"
        e2e = ["setup_s", "ttft_p90_ms", "tpot_p90_ms"]
        layer = ["tick_ms.chat"]
    else:
        conf, traffic, cell = "tiny-moe-train", "tiny-train", \
            "moe100m-train-skew"
        e2e = ["setup_s", "train_tok_s"]
        layer = ["reshape_ms.train"]
    return {"name": cell, "chips": 1,
            "config": spec.load_json(DATA / f"{conf}.json"),
            "traffic": spec.load_json(DATA / f"{traffic}.json"),
            "limits": spec.load_json(DATA / f"tiny-{kind}-limits.json"),
            "end_to_end": [{"name": n, "unit": "x"} for n in e2e],
            "per_layer": [{"name": n, "unit": "x"} for n in layer]}


def execute(cell: dict, seed: int, tmp_path, seconds: float = 2.0,
            traced: bool = False) -> dict:
    from bench import run
    return run.execute(cell, seed, seconds, traced, time.perf_counter(),
                       trace_dir=Path(tmp_path) / "trace")
