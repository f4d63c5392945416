"""A benchmark cell, built from its files alone.

A cell is ``workloads/<name>.json`` (its traffic: clients, batch, lengths,
algorithm, codecs, policy) naming a configuration ``configs/<config>.json``
(the model as it is run, beside its published numbers).  Nothing here is
specific to one cell: a new cell or configuration is a new file.

The program is reached only through its front door, ``repro.fed.api``:
``RunSpec -> plan() -> build()``.
"""
from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

# the program's dataset seeds are seed * 1000 + client and must fit int32
SEED_MODULUS = 2_000_003


def load_workload(name: str) -> dict:
    path = HERE / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"unknown workload {name!r}: no {path.name} "
                         f"under {path.parent}")
    return json.loads(path.read_text())


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def model_config(conf: dict):
    from repro.configs.base import LoRAConfig, ModelConfig
    m = dict(conf["model"])
    m["pattern"] = tuple(m["pattern"])
    lora = m.pop("lora", None)
    if lora is not None:
        lora = LoRAConfig(rank=lora["rank"], alpha=lora["alpha"],
                          targets=tuple(lora["targets"]))
    return ModelConfig(lora=lora, **m)


def engine_seed(seed: int) -> int:
    return int(seed) % SEED_MODULUS


def run_spec(wl: dict, cfg, seed: int):
    """The RunSpec of one cell at one seed."""
    from repro.configs.base import FIRMConfig, SchedConfig
    from repro.fed import api
    fc = FIRMConfig(n_objectives=wl["n_objectives"],
                    n_clients=wl["n_clients"],
                    local_steps=wl["local_steps"],
                    batch_size=wl["batch_size"])
    ec = api.EngineConfig(algorithm=wl["algorithm"],
                          prompt_len=wl["prompt_len"],
                          max_new=wl["max_new"],
                          dirichlet_alpha=wl["dirichlet_alpha"],
                          seed=engine_seed(seed),
                          uplink_codec=wl["uplink_codec"],
                          downlink_codec=wl["downlink_codec"],
                          fused_rounds=wl["fused_rounds"])
    return api.RunSpec(model=cfg, firm=fc, engine=ec,
                       sched=SchedConfig(policy=wl["policy"]))


def load(name: str):
    """(workload dict, config dict, ModelConfig) of one cell."""
    wl = load_workload(name)
    conf = load_config(wl["config"])
    return wl, conf, model_config(conf)
