"""What the algorithm needs, from the configuration's fields alone: the
operations and bytes of one decode step and of a prefill, for the roofline
shares. Not what the program happens to move: the weights once (for a routed
model, the experts the batch's tokens reach), the live KV, not the whole
cache. Dispatches on the fields (``num_local_experts``), never on a name.
"""

from __future__ import annotations

import json
from pathlib import Path

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, None: 2.0}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of the device JAX reports. An unknown device is
    an error, not a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return table[device_kind]


def sizes(config: dict) -> dict:
    D = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    KV = int(config.get("num_key_value_heads", H))
    hd = int(config.get("head_dim", D // H))
    return {
        "D": D, "H": H, "KV": KV, "hd": hd,
        "F": int(config["intermediate_size"]),
        "L": int(config["num_hidden_layers"]),
        "V": int(config["vocab_size"]),
        "E": int(config.get("num_local_experts", 0)),
        "k": int(config.get("num_experts_per_tok", 2)),
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def attn_params(s: dict) -> int:
    return s["D"] * s["hd"] * (s["H"] + 2 * s["KV"]) + s["H"] * s["hd"] * s["D"]


def expert_params(s: dict) -> int:
    return 3 * s["D"] * s["F"]


def active_params_per_token(s: dict) -> int:
    """Matmul parameters one token multiplies: attention, its MLP (the top-k
    experts and the router of a routed layer), the output head."""
    mlp = expert_params(s) * (s["k"] if s["E"] else 1) + s["D"] * s["E"]
    return s["L"] * (attn_params(s) + mlp) + s["D"] * s["V"]


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct experts per layer that ``tokens`` tokens reach,
    routing taken as uniform (seeded weights route near uniformly)."""
    if not s["E"]:
        return 1.0
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** max(tokens, 0.0))


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a step over ``tokens`` tokens has to read once."""
    mlp = expert_params(s) * experts_reached(s, tokens) * s["wbytes"]
    router = s["D"] * s["E"] * 2.0
    layer = attn_params(s) * s["wbytes"] + mlp + router
    return s["L"] * layer + s["D"] * s["V"] * s["wbytes"]


def kv_bytes_per_token(s: dict) -> float:
    return 2.0 * s["L"] * s["KV"] * s["hd"] * s["kvbytes"]


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together."""
    s = sizes(config)
    flops = 2.0 * active_params_per_token(s) * batch
    flops += 4.0 * s["L"] * s["H"] * s["hd"] * context_tokens  # q.k and p.v
    nbytes = weight_bytes(s, batch) + kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["D"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    causal attention over each prompt, the weights read once a call."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * (active_params_per_token(s) - s["D"] * s["V"]) * tokens
    flops += 2.0 * s["D"] * s["V"] * len(prompt_lengths)  # the head: last rows only
    flops += 4.0 * s["L"] * s["H"] * s["hd"] * sum(n * (n + 1) / 2.0 for n in prompt_lengths)
    per_call = tokens / max(calls, 1.0)
    nbytes = calls * weight_bytes(s, per_call) + kv_bytes_per_token(s) * tokens
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def roofline_pct(work: dict, measured_s: float, peaks: dict) -> float:
    """A share over 100% is a bug in the count, not a result."""
    least, _ = least_seconds(work, peaks)
    pct = 100.0 * least / measured_s
    if pct > 100.0:
        raise AssertionError(
            f"roofline share {pct:.1f}% > 100%: {work} against {measured_s}s measured"
        )
    return pct
