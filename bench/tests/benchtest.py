"""Helpers for the benchmark's CPU tests: a throw-away checkout root that
holds a tiny cell, written as files the way a later PR would add one."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the program's smoke sizes (repro.configs.mesh1k/mesh2k SMOKE)
SMOKE = {"mesh1k": dict(input_hw=64, in_channels=4, convs_per_block=1,
                        widths=[8, 16, 16]),
         "mesh2k": dict(input_hw=64, in_channels=4, convs_per_block=2,
                        widths=[8, 16, 16])}
# tiny-size limits: the CPU computes fp32 exactly at every precision, so
# program and reference agree to ~1e-7 in loss and ~1e-6 per leaf
TINY_LIMITS = {"loss1_gap": 1e-5, "loss_gap": 1e-5, "grad_gap": 1e-4,
               "change_gap": 1e-4}


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp, arch="mesh1k", data=1, model=1, name="tiny"):
    """A checkout root whose BENCHMARK.json is the real one plus one tiny
    cell `name` of `arch` at smoke size on a data x model mesh; the
    metric readers are the real ones.  Returns the root's path."""
    root = str(tmp)
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = load(os.path.join(BENCH, "configs", f"{arch}.json"))
    cfg.update(SMOKE[arch], name=name)
    traffic = load(os.path.join(
        BENCH, "traffic",
        "mesh2k.4chip.b4.json" if data * model > 1 else
        "mesh1k.1chip.b8.json"))
    traffic.update(batch=4, data=data, model=model,
                   limits=dict(TINY_LIMITS))
    dump(cfg, os.path.join(root, "bench", "configs", f"{name}.json"))
    dump(traffic, os.path.join(root, "bench", "traffic", f"{name}.json"))
    os.symlink(os.path.join(BENCH, "metrics"),
               os.path.join(root, "bench", "metrics"))
    bench["configs"].append({"name": name, "source": "smoke",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": name, "chips": data * model,
                               "why": "CPU test"})
    for real in ("configs", "traffic", "models"):
        os.makedirs(os.path.join(root, "bench", real), exist_ok=True)
        for f in os.listdir(os.path.join(BENCH, real)):
            dst = os.path.join(root, "bench", real, f)
            if not os.path.exists(dst):
                os.symlink(os.path.join(BENCH, real, f), dst)
    dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
