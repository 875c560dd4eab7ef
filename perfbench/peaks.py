"""Published peaks of the chips the benchmark may run on, and the
arithmetic that turns a configuration's shapes into operations and bytes.

The peaks are keyed by JAX's ``device_kind``. A device that is not in the
table is an error, never a default.

The arithmetic follows the program's ``runtime/perf_model.py``
(``matmul_params``, ``step_hbm_bytes``) and is kept here so that no later PR
can move the yardstick; the original is listed in PERF.md for a later PR to
delete or to import from here.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks recorded for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def layer_matmul_params(m) -> int:
    """Weights of the linear layers of the whole stack (no embedding, no
    head, no norms): what every token is multiplied by."""
    attn = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim \
        + m.n_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return m.n_layers * (attn + mlp)


def head_params(m) -> int:
    return m.d_model * m.vocab_size


def kv_bytes_per_token(m) -> int:
    return 2 * m.n_layers * m.n_kv_heads * m.head_dim * itemsize(m.dtype)


def decode_step_min_bytes(m, context_lens) -> float:
    """The least bytes one decode step has to move through HBM: every
    linear layer's weights and the head once, and the keys and values of
    every row's context once. Activations, the embedding rows and the
    pool's writes are left out, so this is a lower bound."""
    weights = (layer_matmul_params(m) + head_params(m)) * itemsize(m.dtype)
    return weights + kv_bytes_per_token(m) * float(sum(context_lens))
