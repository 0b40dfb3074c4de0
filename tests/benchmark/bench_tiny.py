"""A tiny benchmark tree of its own: BENCHMARK.json, configurations and
mixes at a size the CPU runs in seconds, beside the real per-layer
readers, so a whole run can be rehearsed without the chip."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tiny_tree(dst: str, keys_per_partition: int = 4096,
              clients: int = 4) -> str:
    """Copy BENCHMARK.json and the readers; shrink the configurations
    and the mixes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(dst, "benchmark"))
    shutil.copytree(os.path.join(ROOT, "benchmark", "layer_metrics"),
                    os.path.join(dst, "benchmark", "layer_metrics"))
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(dst, "benchmark", sub))
        for name in os.listdir(os.path.join(ROOT, "benchmark", sub)):
            with open(os.path.join(ROOT, "benchmark", sub, name)) as f:
                doc = json.load(f)
            if sub == "configs":
                doc["keys_per_partition"] = keys_per_partition
                doc["partitions"] = 2
            else:
                doc["clients"] = clients
            with open(os.path.join(dst, "benchmark", sub, name),
                      "w") as f:
                json.dump(doc, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
