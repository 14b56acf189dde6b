"""Config registry: ``--arch <id>`` resolution for the 10 assigned
architectures (+ reduced smoke variants) and the paper's own FL configs."""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    INPUT_SHAPES,
    ArchConfig,
    InputShape,
    active_param_count,
    param_count,
)

_ARCH_MODULES = {
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "starcoder2-3b": "starcoder2_3b",
    "starcoder2-7b": "starcoder2_7b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "qwen2.5-14b": "qwen2_5_14b",
    "internvl2-26b": "internvl2_26b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "hubert-xlarge": "hubert_xlarge",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
}

ARCH_IDS = tuple(_ARCH_MODULES)

#: architectures run as federated clients (``repro.models.factory``),
#: outside the dry-run grid
_FL_ARCH_MODULES = {
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

FL_ARCH_IDS = tuple(_FL_ARCH_MODULES)


def get_arch(arch_id: str, smoke: bool = False) -> ArchConfig:
    modules = {**_ARCH_MODULES, **_FL_ARCH_MODULES}
    if arch_id not in modules:
        raise KeyError(f"unknown arch {arch_id!r}; have {list(modules)}")
    mod = importlib.import_module(f"repro.configs.{modules[arch_id]}")
    return mod.smoke() if smoke else mod.CONFIG


def valid_pairs():
    """The 10x4 assignment grid with skip annotations.

    Yields (arch_id, shape_name, runnable: bool, skip_reason: str).
    """
    for aid in ARCH_IDS:
        cfg = get_arch(aid)
        for sname, shape in INPUT_SHAPES.items():
            if shape.mode == "decode" and not cfg.supports_decode():
                yield aid, sname, False, "encoder-only: no decode step"
            elif sname == "long_500k" and not cfg.subquadratic():
                yield aid, sname, False, "full attention: long_500k requires sub-quadratic"
            else:
                yield aid, sname, True, ""
