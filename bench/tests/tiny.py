"""A deployment small enough for the CPU: every policy the reference
knows, on 8 instances x 2 slots, in a root of its own that holds only new
files (``BENCHMARK.json``, ``bench/configs``, ``bench/traffic``)."""

from __future__ import annotations

import json
from pathlib import Path

APP = {"name": "xlb-service-model", "n_layers": 2, "d_model": 128,
       "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
       "vocab": 512, "ffn_act": "swiglu", "norm_eps": 1e-05,
       "rope_theta": 10000.0, "dtype": "bfloat16"}

CONFIG = {
    "source": "a test deployment", "engine": "xlb", "instances": 8,
    "slots": 2, "admit_batch": 8, "application": APP,
    "limits": {"logit_gap": 0.12},
    "services": [
        {"name": "a", "rules": [{"header": "path", "value": "/a",
                                 "cluster": "a"}]},
        {"name": "b", "rules": [{"header": "path", "value": None,
                                 "cluster": "b"}]},
        {"name": "c", "rules": [
            {"header": "user", "value": "jason", "cluster": "c2"},
            {"header": "path", "value": None, "cluster": "c13"}]},
        {"name": "d", "rules": [{"header": "path", "value": None,
                                 "cluster": "d"}]},
        {"name": "e", "rules": [{"header": "path", "value": None,
                                 "cluster": "e"}]},
        {"name": "f", "rules": [{"header": "path", "value": None,
                                 "cluster": "f"}]},
    ],
    "clusters": [
        {"name": "a", "lanes": [0, 2], "policy": "least_request"},
        {"name": "b", "lanes": [2, 2], "policy": "rr"},
        {"name": "c2", "endpoints": [4], "policy": "least_request"},
        {"name": "c13", "subsets": [
            {"name": "v1", "endpoints": [5], "weight": 30},
            {"name": "v3", "endpoints": [6], "weight": 70}],
         "policy": "weighted"},
        {"name": "d", "endpoints": [0, 7], "policy": "maglev"},
        {"name": "e", "endpoints": [1, 2, 3], "policy": "affinity"},
        {"name": "f", "endpoints": [4, 5], "policy": "random"},
    ],
}

_CLASSES = [{"service": s, "share": 1.0,
             "headers": {"path": f"/{s}", "user": "$user"}}
            for s in "abcdef"]
_USERS = {"count": 50, "zipf_s": 1.0, "named": {"jason": 0.1}}
MIXES = {
    "tiny-open": {"loop": "open", "rate_rps": 40.0, "max_len": 6,
                  "warmup_s": 0.3, "msg_bytes": [64, 1024],
                  "users": _USERS, "classes": _CLASSES},
    "tiny-closed": {"loop": "closed", "clients_per_slot": 1, "max_len": 6,
                    "warmup_s": 0.3, "msg_bytes": [64, 1024],
                    "users": _USERS, "classes": _CLASSES},
}
CELLS = ["tiny.tiny-open", "tiny.tiny-closed"]
SEED = 2**31 + 77            # above 32 signed bits, as the driver's are


def write_root(root: Path) -> Path:
    """Write the tiny deployment's files under ``root`` and return it."""
    bm = json.loads((Path(__file__).resolve().parents[2]
                     / "BENCHMARK.json").read_text())
    bm["workloads"] = [{"name": c, "config": c.split(".")[0],
                        "traffic": c.split(".")[1], "chips": 1, "why": "t"}
                       for c in CELLS]
    for m in bm["end_to_end"]:
        m.pop("workloads", None)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(CONFIG))
    for name, mix in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    return root
