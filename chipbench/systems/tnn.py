"""The TNN serve path (``serve/tnn_engine.TNNEngine``): frames encoded as
spike volleys in, post-WTA spike times out. The harness modules written for
it, bound to the contract of ``systems/__init__.py``."""

from typing import Dict

import jax.numpy as jnp

from harness import check, reference, system, traffic

pool = traffic.volley_pool
payload = traffic.stream
init_params = reference.init_weights
engine = system.ProgramEngine
compare = check.compare


def control_engine(params, config: Dict) -> system.ControlEngine:
    """The reference with its weights in bfloat16, below the float32 that
    the configuration states."""
    return system.ControlEngine(params, config, wdtype=jnp.bfloat16)


def stateful(config: Dict) -> bool:
    """Whether outputs depend on what else was served: learning, or a
    recurrent layer's carry (the check then replays the served order)."""
    return bool(config.get("learn")) or any(
        lay.get("recurrent") for lay in config["layers"])
