"""The system under test for KWT: the program's ``StreamServer`` over the
benchmark's weights.

This is the family's one module that imports the program (``src/repro``).
The program takes the weights in the benchmark's layout and serves its
``compute_dtype`` copy.
"""

from __future__ import annotations

from typing import Optional

from bench.serve import server_seed


def kwt_config(model: dict):
    from repro.models import kwt
    return kwt.KWTConfig(**model)


def build_server(config: dict, w: dict, n_slots: int, seed: int,
                 vad: Optional[dict]):
    """A ``StreamServer`` at the configuration's hop with the compiled
    tick (a KWT server refuses a VAD-gated traffic mix)."""
    from repro.serving import (CompiledTickConfig, DecisionConfig,
                               ObsConfig, StreamServer, VADConfig)
    serving = config["serving"]
    return StreamServer(
        w, kwt_config(config["model"]), hop=serving["hop"], slots=n_slots,
        use_kernel=False, decision=DecisionConfig(**config["decision"]),
        vad=VADConfig(**vad) if vad is not None else None,
        compiled=CompiledTickConfig(block=serving["block"]),
        obs=ObsConfig(), seed=server_seed(seed))
