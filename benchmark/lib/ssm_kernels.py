"""The Nemotron-H cell's state-space layers in a traced run: the least
time the scan's work could take, and which phases are the mixer's. What
the ``kernels.ssm_scan_roofline.serve`` and ``serving_engine.ssm_share.
serve`` readers share.

The scan kernel is told by its PHASE (``ssm.scan``, ``lib/phases.py``'s
join of the device trace with the program's own instruction-to-phase
map), not by its operands. Its work per step, from the
``serving.ragged_step`` span's attributes (the program's, counted on the
host from the arrays it builds):

* bytes = ``state_rows`` x ``state_layers`` x heads x head_dim x state x
  the state's itemsize x 2: every live row's state read once and written
  once, in every state layer;
* FLOPs = ``ssm_tokens`` x ``state_layers`` x heads x head_dim x state x 4:
  the recurrence's own update (decay and outer product, 3) and read-out
  (1) a token.

The counts are the same whatever algorithm implements the scan (chunked,
token by token), so no implementation can read over 100 %.
"""
from __future__ import annotations

from . import phases, serve_bytes

STEP_ATTRS = ("state_rows", "state_layers", "ssm_tokens", "ssm_heads",
              "ssm_head_dim", "ssm_state_size")
SSM_PHASES = ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.gate")
ITEMSIZE = {"float32": 4, "bfloat16": 2}


def scan_steps(record, cell):
    """The traced part's ``serving.ragged_step`` spans that carry the
    state attributes: a program without them gives nothing."""
    return [s for s in serve_bytes.traced_steps(record, cell)
            if all(k in s["args"] for k in STEP_ATTRS)]


def scan_least_s(args, config, peaks) -> float:
    """Least seconds of ONE step's scans, all state layers."""
    cell = args["ssm_heads"] * args["ssm_head_dim"] * args["ssm_state_size"]
    item = ITEMSIZE[config.get("model_kwargs", {}).get("ssm_state_dtype",
                                                       "float32")]
    bytes_ = args["state_rows"] * args["state_layers"] * cell * item * 2
    flops = args["ssm_tokens"] * args["state_layers"] * cell * 4.0
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops"])


def phase_seconds(record, cell, names):
    """Device seconds of the serving step's ``names`` phases in the
    traced window, or None where the program has none of them."""
    t = phases.table(record, cell, "serve")
    if not t or not any(p in t["seconds"] for p in names):
        return None
    return sum(sum(t["seconds"][p].values()) for p in names
               if p in t["seconds"])
