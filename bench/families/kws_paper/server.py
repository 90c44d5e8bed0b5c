"""The system under test for the paper net: the program's ``StreamServer``
over the family's folded weights.

This is the family's one module that imports the program (``src/repro``).
It turns the benchmark's own weights into the program's folded, packed
parameters and builds the server the configuration states.
"""

from __future__ import annotations

from typing import Optional

from bench.serve import server_seed


def kws_config(model: dict):
    from repro.models import kws
    return kws.KWSConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                            for k, v in model.items()})


def build_server(config: dict, w: dict, n_slots: int, seed: int,
                 vad: Optional[dict]):
    """A ``StreamServer`` at the configuration's hop with the compiled
    tick, over the benchmark's weights ``w``."""
    from repro.models import kws
    from repro.serving import (CompiledTickConfig, DecisionConfig,
                               ObsConfig, StreamServer, VADConfig)
    model, serving, silicon = (config["model"], config["serving"],
                               config["silicon"])
    cfg = kws_config(model)
    names = [f"conv{i}" for i in range(len(model["channels"]))]
    hw = kws.pack_hw_params(kws.HWParams(
        w_bin=dict(zip(names, w["w"])), bias=dict(zip(names, w["bias"])),
        flip=dict(zip(names, w["flip"])), fc_w=w["fc_w"], fc_b=w["fc_b"]),
        cfg)
    offsets = None
    if w["offsets"] is not None:
        offsets = dict(zip(names[1:], w["offsets"][1:]))
    vcfg = None
    if vad is not None:
        vcfg = VADConfig(**vad)
    return StreamServer(
        hw, cfg, hop=serving["hop"], slots=n_slots, chip_offsets=offsets,
        sa_noise_std=float(silicon["sa_noise_std"]),
        use_kernel=serving["use_kernel"],
        decision=DecisionConfig(**config["decision"]), vad=vcfg,
        compiled=CompiledTickConfig(block=serving["block"]),
        obs=ObsConfig(), seed=server_seed(seed))
