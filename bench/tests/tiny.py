"""Test-only override: a cell of the manifest cut to a size the CPU runs
in seconds. The depth, slots and positions shrink, the widths shrink to
512 (wide enough that logits spread as at full width: the embedding's
0.02 std times sqrt(512)), and the traffic keeps its shape at shorter
lengths; everything else (generator, system, probe, check, readers) is
the benchmark's own."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import manifest

TINY_MODELS = {
    "dense": {"num_layers": 2, "d_model": 512, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 128, "d_ff": 1024,
              "vocab_size": 512},
    "ssm": {"num_layers": 2, "d_model": 512, "vocab_size": 512,
            "ssm": {"d_state": 32, "head_dim": 64, "expand": 2,
                    "chunk_size": 16, "n_groups": 1, "conv_width": 4,
                    "dt_min": 0.001, "dt_max": 0.1}},
}
SLOTS, POSITIONS = 4, 64
SHRINK = 8
DATA = Path(__file__).resolve().parent / "data"


def kept_mix(name: str) -> dict:
    """A traffic mix kept for the tests alone (``data/<name>.json``)."""
    return json.loads((DATA / f"{name}.json").read_text())


def _shrink(spec: dict) -> dict:
    out = dict(spec)
    for k in ("min", "max"):
        out[k] = max(1, spec[k] // SHRINK)
    return out


def tiny_cell(name: str, manifest_obj=None, root=manifest.ROOT,
              traffic: dict = None) -> manifest.Cell:
    """The cell ``name`` at the tiny size; ``traffic`` replaces its mix."""
    cell = copy.deepcopy(manifest.find_cell(name, root, manifest_obj))
    if traffic is not None:
        cell.traffic = copy.deepcopy(traffic)
    fam = cell.config["model"]["family"]
    cell.config["model"].update(copy.deepcopy(TINY_MODELS[fam]))
    cell.config["serving"].update(slots=SLOTS, positions=POSITIONS)
    t = cell.traffic
    t["length_scale"] = t["length_scale"] / SHRINK
    t["prompt_tokens"] = _shrink(t["prompt_tokens"])
    t["output_tokens"] = _shrink(t["output_tokens"])
    t["max_total_tokens"] = POSITIONS - 1
    t["clients"] = min(t["clients"], 2 * SLOTS * cell.chips)
    t["preroll_s"] = 0.5
    if t["loop"] == "open":
        t["rate_per_s"] = 4.0
    return cell
