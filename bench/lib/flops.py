"""Work a training step requires, counted from the model's shapes.

Only matrix products count (the usual MFU convention): the projections,
the causal attention products, the SwiGLU feed-forward, the image
projector and the output head over the positions the loss reads.
The embedding gather costs no multiply.  Nothing is counted for
recomputation (remat) or for logits the loss never reads, so the count
is what the model requires, whatever the implementation does.

Backward of ``y = x @ W`` is two products of the forward's size (one for
``dx``, one for ``dW``) where ``x`` depends on the weights, and one where
``x`` is input data (the projector's patches).
"""
from __future__ import annotations


def vlm_train_flops(m: dict, batch: int, seq: int) -> float:
    """Forward + backward FLOPs of one step over ``batch`` sequences of
    ``seq`` positions (``n_patches`` image positions, then text) of the
    model described by the configuration dict ``m``."""
    d, L = m["d_model"], m["n_layers"]
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ff, V, P = m["d_ff"], m["vocab"], m["n_patches"]
    text = seq - P
    per_token_layer = (2 * d * h * hd            # q
                       + 2 * 2 * d * kv * hd     # k, v
                       + 2 * h * hd * d          # o
                       + 3 * 2 * d * ff)         # gate, up, down
    # Causal attention: position i attends to i + 1 keys; QK^T and PV.
    attn_per_layer = 2 * 2 * h * hd * (seq * (seq + 1) // 2)
    layers_fwd = L * (seq * per_token_layer + attn_per_layer)
    projector_fwd = 2 * P * d * d
    # The loss reads the logits of ``text`` positions (the last patch
    # position through the second-to-last text position).
    head_fwd = 2 * text * d * V
    per_seq = 3 * (layers_fwd + head_fwd) + 2 * projector_fwd
    return float(batch * per_seq)

