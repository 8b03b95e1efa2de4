"""deepseek-v2-lite-16b-ep8 — one chip's share of DeepSeek-V2-Lite.

The deployment: 16 chips in 2 pipeline stages, each stage an 8-way
expert-parallel group.  Stage 0 holds layer 0 (dense) and MoE layers 1-13;
within it each chip holds 8 of every layer's 64 routed experts and computes
MLA, the router and the 2 shared experts whole, as every rank does.  This
config is stage 0, rank 0: 14 layers, routed experts 0-7.  The router
keeps its 64 outputs and top-6; assignments to experts 8-63 are left to
the ranks that hold them (no exchange runs on one chip).  Every width is
the published model's (``deepseek_v2_lite``).
"""

import dataclasses

from repro.configs.deepseek_v2_lite import CONFIG as FULL

CONFIG = FULL.with_(
    name="deepseek-v2-lite-16b-ep8",
    num_layers=14,
    stack_pattern=(("mla_dense", 1), ("mla_moe", 13)),
    moe=dataclasses.replace(FULL.moe, first_expert=0, experts_held=8),
)
