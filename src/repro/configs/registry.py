"""Architecture registry: --arch <id> -> ModelConfig.

All 10 assigned architectures plus the paper's own small GFN policies are
selectable; reduced smoke variants instantiate on CPU.  ``POLICY_ARCHS``
lists the LM architectures that serve as GFlowNet policies
(``core.policies.make_lm_policy``) rather than through ``models/lm.py``.
"""
from __future__ import annotations

from ..models.config import SHAPES, ModelConfig, ShapeConfig, cell_is_runnable
from . import (command_r_35b, command_r_plus_104b, hymba_1_5b,
               moonlight_16b_a3b, qwen2_5_32b, qwen2_72b, qwen2_moe_a2_7b,
               qwen2_vl_72b, qwen3_moe_30b_a3b, rwkv6_1_6b, whisper_medium)

_MODULES = {
    m.ARCH_ID: m for m in (
        qwen2_5_32b, command_r_plus_104b, qwen2_72b, command_r_35b,
        hymba_1_5b, rwkv6_1_6b, whisper_medium, qwen2_moe_a2_7b,
        qwen3_moe_30b_a3b, qwen2_vl_72b)
}

ARCH_IDS = list(_MODULES)

POLICY_ARCHS = {m.ARCH_ID: m for m in (moonlight_16b_a3b,)}


def get_config(arch_id: str, smoke: bool = False) -> ModelConfig:
    mod = _MODULES[arch_id]
    return mod.smoke_config() if smoke else mod.config()


def get_shape(shape_id: str) -> ShapeConfig:
    return SHAPES[shape_id]


def all_cells():
    """All 40 (arch x shape) cells with runnability verdicts."""
    out = []
    for a in ARCH_IDS:
        cfg = get_config(a)
        for s in SHAPES.values():
            ok, why = cell_is_runnable(cfg, s)
            out.append((a, s.name, ok, why))
    return out
