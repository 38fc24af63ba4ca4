"""Everything that depends on the shape of a configuration's block is exported
by its reference file, ``benchmarks/reference/<name>.py`` (the configuration
file's ``reference`` key), and ``lib/`` asks it here:

    top_leaves(cfg), layer_leaves(cfg, index)    the leaf table  -> lib/weights.py, lib/program.py
    sequence_logits(...), batch_loss_and_grads(...), adamw_update(...)
                                                 the walk        -> lib/drivers/
    matmul_params(cfg, depth), attention_passes(cfg, depth), head_dim(cfg)
                                                 the counts      -> lib/flops.py, metrics/

``benchmarks/README.md`` says what each has to be; ``reference/decoder.py`` is
the worked example."""

from __future__ import annotations

import importlib
from typing import Any, Dict


def reference(cfg: Dict[str, Any]) -> Any:
    """The reference module of a configuration."""
    return importlib.import_module(f"reference.{cfg['reference']}")
