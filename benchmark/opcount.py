"""Operations and bytes the algorithm NEEDS, from shapes: the numerators of
MFU and of a roofline share. Kept with the benchmark so that no PR that
claims a gain can change them. Work the program does beyond this (padding
rows of a chunk, a table gathered at its full width, recomputation) is not
counted: it is what the share is meant to expose.

All functions take the configuration file's published keys.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterable, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The published peaks of a device kind; an unknown kind is an error,
    never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has: {sorted(table)})")
    return table[device_kind]


def window_of(cf: Dict[str, Any]) -> int:
    """The sliding window in use, 0 for full causal attention."""
    return int(cf["sliding_window"]) if cf["use_sliding_window"] else 0


def layer_matmul_params(cf: Dict[str, Any]) -> int:
    """Weights of one layer that a token is multiplied by."""
    d, f = cf["hidden_size"], cf["intermediate_size"]
    q = cf["num_attention_heads"] * cf["head_dim"]
    kv = cf["num_key_value_heads"] * cf["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cf: Dict[str, Any]) -> int:
    return cf["hidden_size"] * cf["vocab_size"]


def weight_bytes(cf: Dict[str, Any]) -> int:
    """Bytes of the served weights: layers, embedding and head (norm gains
    and biases included, they are small but real)."""
    b = BYTES[cf["precision"]["weights"]]
    d, L = cf["hidden_size"], cf["num_hidden_layers"]
    small = 2 * d * L + d
    if cf["attention_bias"]:
        small += L * cf["head_dim"] * (cf["num_attention_heads"]
                                       + 2 * cf["num_key_value_heads"])
    emb = cf["vocab_size"] * d * (1 if cf["tie_word_embeddings"] else 2)
    return b * (L * layer_matmul_params(cf) + emb + small)


def kv_bytes_per_token(cf: Dict[str, Any]) -> int:
    """Keys and values of one position over all layers, as cached."""
    b = BYTES[cf["precision"]["activations"]]
    return (2 * cf["num_key_value_heads"] * cf["head_dim"] * b
            * cf["num_hidden_layers"])


def keys_seen(pos: int, n: int, window: int = 0) -> int:
    """Keys the n new tokens at positions pos..pos+n-1 attend to, summed:
    token at position p sees min(p + 1, window) keys (causal, itself
    included)."""
    total = 0
    for p in range(pos, pos + n):
        total += min(p + 1, window) if window else p + 1
    return total


def attention_flops(cf: Dict[str, Any], keys: int) -> int:
    """Score and value products for ``keys`` query-key pairs in one layer:
    2 matmuls x 2 FLOPs per multiply-add x heads x head size."""
    return 4 * cf["num_attention_heads"] * cf["head_dim"] * keys


def decode_step_needs(cf: Dict[str, Any],
                      rows: Iterable[Tuple[int, int, int]]) -> Dict[str, int]:
    """What one engine step needs. ``rows``: per active request (pos, n,
    samples): tokens already cached, tokens fed this step, and 1 if the
    step's last token yields logits that are sampled.

    FLOPs: every fed token through every layer's matrices, its attention
    over the keys it may see, and the head for the sampled rows. Bytes: the
    layers' weights once, the head once if any row samples, each row's
    valid (in-window) keys and values read once and the new ones written,
    the fed tokens' embedding rows, the sampled logits in float32."""
    L, window = cf["num_hidden_layers"], window_of(cf)
    wb = BYTES[cf["precision"]["weights"]]
    fed = sampled = keys = kv_read = 0
    for pos, n, samples in rows:
        fed += n
        sampled += 1 if samples else 0
        keys += keys_seen(pos, n, window)
        kv_read += min(pos + n, window) if window else pos + n
    flops = (2 * L * layer_matmul_params(cf) * fed
             + L * attention_flops(cf, keys)
             + 2 * head_params(cf) * sampled)
    nbytes = (wb * L * layer_matmul_params(cf)
              + (wb * head_params(cf) if sampled else 0)
              + kv_bytes_per_token(cf) * (kv_read + fed)
              + wb * cf["hidden_size"] * fed
              + 4 * cf["vocab_size"] * sampled)
    return {"flops": flops, "bytes": nbytes, "fed": fed, "sampled": sampled}


def least_seconds(needs: Dict[str, int], peaks: Dict[str, Any]) -> Dict[str, Any]:
    """The roofline: the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s, and which of the two binds."""
    t_f = needs["flops"] / peaks["bf16_flops_per_s"]
    t_b = needs["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "flops_s": t_f, "bytes_s": t_b,
            "bound": "compute" if t_f >= t_b else "memory"}
