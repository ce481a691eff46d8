"""Smoke-size cells for the CPU tests: the committed configuration and
traffic files with their sizes cut down inside the test."""
from __future__ import annotations

import copy

from bench import spec

SMOKE_SIZES = {
    "stablelm-3b": dict(hidden_size=128, intermediate_size=256,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=4, vocab_size=512),
    "qwen3-32b": dict(hidden_size=128, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=32, vocab_size=512),
}
SMOKE_TRAFFIC = dict(
    clients=4, slots=4, table_size=64,
    prompt_len={"lognormal": {"mean": 11.0, "sd": 4.0}, "bins": [8, 16]},
    output_len={"lognormal": {"mean": 8.0, "sd": 4.0}, "clip": [4, 16]})


def smoke_cell(workload: str, weights: str | None = None):
    """The committed cell with smoke sizes, the jnp codec and, if given,
    another weight format."""
    cell = copy.deepcopy(spec.cell(workload))
    model = cell.config_name.split(".")[0]
    cell.config.update(SMOKE_SIZES[model])
    cell.config["serving"]["codec"] = "reference"
    if weights is not None:
        cell.config["serving"]["weights"] = weights
    cell.traffic.update(SMOKE_TRAFFIC)
    return cell
