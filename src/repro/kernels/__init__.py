"""Pallas TPU kernels for the perf-critical hot spots:

  * flash_attention  — causal GQA/SWA/softcap flash attention (training &
                       prefill compute hot spot)
  * decode_attention — flash-decode over the KV slot table with fused DAC
                       hit-signal (per-slot attention mass) extraction
  * policy_step      — fused rank-policy step (find + plan + promote
                       + wipe): a dense in-place pass over blocks of
                       whole lane-padded rank rows, or a tiled segmented
                       pass for rows too wide for VMEM; serves every rank
                       policy — Climb, AdaptiveClimb, DAC, the budgeted
                       tier step — via a traced-in control-law callback
                       and backs the engine's ``use_pallas`` replay path
                       (``"auto"``, the default: the compiled dense kernel
                       on a TPU where it fits, jnp elsewhere)

Each has a pure-jnp oracle (ref.py for the attention kernels;
core.policy.rank_step *is* the oracle for policy_step); ops.py exposes
jit'd wrappers whose ``interpret=None`` resolves per backend via
``policy_step.resolve_interpret`` (env-overridable with
``REPRO_PALLAS_INTERPRET``).
"""
from . import ops, policy_step, ref

__all__ = ["ops", "policy_step", "ref"]
