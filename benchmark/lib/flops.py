"""Operations one forward needs, counted from the configuration's shapes. A
reference module names its count as ``forward_flops_per_row``, so another
architecture brings its own with its reference.

Matrix products only (2 x m x n x k each): patch embedding, the four
projections and the two attention products of every block, the MLP, and the
output projection. Elementwise work (LayerNorm, softmax, GELU, residuals) is
left out, which is why XLA's own count reads 0.4-0.5% higher."""

from __future__ import annotations


def vit_forward_flops_per_row(cfg: dict) -> float:
    w, i = cfg["hidden_size"], cfg["intermediate_size"]
    p, c = cfg["patch_size"], cfg["num_channels"]
    patches = (cfg["image_size"] // p) ** 2
    t = patches + 1
    block = (2 * t * w * 3 * w      # q, k, v
             + 2 * t * t * w        # scores, over all heads
             + 2 * t * t * w        # weighted values
             + 2 * t * w * w        # attention output
             + 2 * 2 * t * w * i)   # fc1, fc2
    return float(2 * patches * (p * p * c) * w
                 + cfg["num_hidden_layers"] * block
                 + 2 * w * cfg["projection_dim"])
