"""Entry ``replay_stream``: one ``Engine().replay_stream`` call over the
harness's chunks, as a user makes it, with no lowering chosen.

The configuration gives the policy by name (``"policy"``) and parameters
(``"policy_params"``), and the capacity ``K``; the spec string the engine
parses is built from them here, so the engine and the plain reference are
given the same parameters.
"""
from __future__ import annotations


def policy_spec(config: dict) -> str:
    params = ",".join(f"{k}={v}" for k, v in config["policy_params"].items())
    return f"{config['policy']}({params})"


def drive(config: dict, chunks, engine=None):
    """The run's result: an object whose ``metrics`` hold per-lane
    ``requests``, ``hits``, ``bytes_total`` and ``bytes_missed``.
    ``engine`` stands in for ``repro.core.Engine()`` (controls, faults)."""
    if engine is None:
        from repro.core import Engine
        engine = Engine()
    return engine.replay_stream(policy_spec(config), chunks, config["K"])
