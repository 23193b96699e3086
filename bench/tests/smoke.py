"""A benchmark of SMOKE-width cells, laid out as the real one is, for
rehearsals on the CPU; beside the real cells at SMOKE width, cells of two
toy architectures that the real benchmark does not have: a token MLP under
SGD (``toy_mlp.py``) and the same MLP under Adam in a step that donates its
state (``toy_adam.py``)."""
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
CELLS = {"caltech101.ckpt_preempt_x3": "ckpt_preempt_x3",
         "caltech101.device_resize": "device_resize"}

TOY_CELL = "smoke.toy"
TOY_CONFIG = {"name": "smoke_toy",
              "model": {"arch": "toy_mlp", "vocab": 64, "seq": 16,
                        "hidden": 32, "lr": 0.5},
              "batch": 8, "n_records": 192, "records_per_shard": 16,
              "limits": {"loss_gap": 1e-5, "grad_gap": 1e-4,
                         "delta_gap": 1e-4}}
TOY_ADAM_CELL = "smoke.toy_adam"
TOY_ADAM_CONFIG = {"name": "smoke_toy_adam",
                   "model": {"arch": "toy_adam", "vocab": 64, "seq": 16,
                             "hidden": 32, "lr": 0.01, "b1": 0.9,
                             "b2": 0.999, "eps": 1e-8},
                   "batch": 8, "n_records": 192, "records_per_shard": 16,
                   "limits": {"loss_gap": 1e-5, "grad_gap": 1e-4,
                              "delta_gap": 1e-4}}
TOYS = {TOY_CELL: TOY_CONFIG, TOY_ADAM_CELL: TOY_ADAM_CONFIG}
# saves and preemptions, far enough apart at the toy's ~1 ms steps that a
# save drains before the next preemption abandons what is still queued
TOY_TRAFFIC = {"about": "tests",
               "ckpt": {"engine": "asyncbb", "every_steps": 50,
                        "max_pending": 2},
               "preempt": {"first_step": 30, "every_steps": 100,
                           "deadline_s": 30}}
# the metrics the toy cell reports: all but the image pipeline's
TOY_LEAVES_OUT = {"decode_busy_ms", "resize_roofline"}


def smoke_config(name: str, batch: int) -> dict:
    return {"name": name, "model": dict(SMOKE_MODEL), "batch": batch,
            "n_images": 96, "images_per_shard": 8, "image_hw": 72,
            "channels": 3, "n_classes": 10,
            "limits": {"pixel_gap": 1e-5, "loss_gap": 1e-4,
                       "grad_gap": 1e-3, "delta_gap": 1e-3}}


def build(root: Path) -> Spec:
    """Write a smoke benchmark under ``root``: the real metric readers and
    architectures, the real traffic mixes at a cadence a few seconds can
    hold, a SMOKE configuration of the model, and the toy architectures'
    modules, configurations, traffic and cells."""
    real = Spec(REPO)
    home = root / "bench"
    shutil.copytree(real.home / "metrics", home / "metrics")
    shutil.copytree(real.home / "arch", home / "arch",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for toy in ("toy_mlp.py", "toy_adam.py"):
        shutil.copy(Path(__file__).with_name(toy), home / "arch" / toy)
    (home / "traffic").mkdir(parents=True)
    (home / "configs").mkdir()
    for name in CELLS.values():
        t = real.traffic(name)
        if t["ckpt"]:
            t["ckpt"]["every_steps"] = 4
        if t["preempt"]:
            t["preempt"].update(first_step=7, every_steps=9)
        (home / "traffic" / f"{name}.json").write_text(json.dumps(t))
    (home / "traffic" / "toy_tokens.json").write_text(json.dumps(TOY_TRAFFIC))
    (home / "configs" / "smoke_caltech.json").write_text(
        json.dumps(smoke_config("smoke_caltech", 8)))
    for cfg in TOYS.values():
        (home / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    data = dict(real.data)
    data["configs"] = [{"name": c, "source": "tests", "reduced": [],
                        "why": "tests", "file": f"bench/configs/{c}.json"}
                       for c in ["smoke_caltech"]
                       + [cfg["name"] for cfg in TOYS.values()]]
    data["workloads"] = [
        {"name": f"smoke.{t}", "config": "smoke_caltech", "traffic": t,
         "chips": 1, "why": "tests"} for t in CELLS.values()]
    data["workloads"] += [{"name": cell, "config": cfg["name"],
                           "traffic": "toy_tokens", "chips": 1, "why": "tests"}
                          for cell, cfg in TOYS.items()]
    cells = {c: f"smoke.{t}" for c, t in CELLS.items()}
    for group in ("end_to_end", "per_layer"):
        data[group] = [
            dict(m, workloads=[cells[w] for w in m["workloads"]]
                 + (list(TOYS) if m["name"] not in TOY_LEAVES_OUT else []))
            if "workloads" in m else m for m in data[group]]
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return Spec(root)
