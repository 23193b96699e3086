"""A benchmark of SMOKE-width cells, laid out as the real one is, for
rehearsals on the CPU."""
import json
import shutil
from pathlib import Path

from bench.peaks import Peaks
from bench.spec import REPO, Spec

CPU_PEAKS = Peaks(bf16_flops=1e12, int8_ops=2e12, hbm_bytes_s=1e11,
                  hbm_bytes=1e10, ici_bits_s=1e11, source="made up, CPU tests")

SMOKE_MODEL = {"arch": "alexnet", "in_hw": 64, "channels": 3, "n_classes": 10,
               "filters": [16, 32, 48, 32, 32], "fc": [256, 256],
               "lr": 0.0001}


# each real cell and its traffic mix
CELLS = {"caltech101.ckpt_preempt": "ckpt_preempt",
         "caltech101.device_resize": "device_resize"}


def smoke_config(name: str, batch: int) -> dict:
    return {"name": name, "model": dict(SMOKE_MODEL), "batch": batch,
            "n_images": 96, "images_per_shard": 8, "image_hw": 72,
            "channels": 3, "n_classes": 10,
            "limits": {"pixel_gap": 1e-5, "loss_gap": 1e-4,
                       "grad_gap": 1e-3, "delta_gap": 1e-3}}


def build(root: Path) -> Spec:
    """Write a smoke benchmark under ``root``: the real metric readers, the
    real traffic mixes at a cadence a few seconds can hold, and a SMOKE
    configuration of the model."""
    real = Spec(REPO)
    home = root / "bench"
    shutil.copytree(real.home / "metrics", home / "metrics")
    (home / "traffic").mkdir(parents=True)
    (home / "configs").mkdir()
    for name in CELLS.values():
        t = real.traffic(name)
        if t["ckpt"]:
            t["ckpt"]["every_steps"] = 4
        if t["preempt"]:
            t["preempt"].update(first_step=7, every_steps=9)
        (home / "traffic" / f"{name}.json").write_text(json.dumps(t))
    (home / "configs" / "smoke_caltech.json").write_text(
        json.dumps(smoke_config("smoke_caltech", 8)))
    data = dict(real.data)
    data["configs"] = [{"name": "smoke_caltech", "source": "tests",
                        "reduced": [], "why": "tests",
                        "file": "bench/configs/smoke_caltech.json"}]
    data["workloads"] = [
        {"name": f"smoke.{t}", "config": "smoke_caltech", "traffic": t,
         "chips": 1, "why": "tests"} for t in CELLS.values()]
    cells = {c: f"smoke.{t}" for c, t in CELLS.items()}
    for group in ("end_to_end", "per_layer"):
        data[group] = [dict(m, workloads=[cells[w] for w in m["workloads"]])
                       if "workloads" in m else m for m in data[group]]
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Spec(root)
