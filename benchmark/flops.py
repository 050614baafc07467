"""Operations a training step needs, from shapes alone.

Model FLOPs as the `choosing-metrics` and `on-chip-measurement` guides
define them: the forward and backward passes of the matrix products and of
causal attention; recomputed operations are not credited, and neither are
element-wise work, norms, softmax or the optimizer."""
from __future__ import annotations


def forward_flops_per_token(*, layers: int, hidden: int, heads: int,
                            kv_heads: int, head_dim: int, ffn: int,
                            vocab: int, seq: int, glu: bool = False) -> float:
    """Multiply-adds count 2. Per token and layer: the q, kv and output
    projections, the MLP's two (GLU: three) products, and causal
    attention's two products over the (seq + 1) / 2 keys a position sees
    on average. Once per token: the head over the vocabulary (the
    embedding lookup is a gather, not a product)."""
    q_out = heads * head_dim
    proj = 2 * hidden * (q_out + 2 * kv_heads * head_dim) + 2 * q_out * hidden
    mlp = 2 * hidden * ffn * (3 if glu else 2)
    attn = 2 * 2 * q_out * (seq + 1) / 2
    return layers * (proj + mlp + attn) + 2 * hidden * vocab


def train_flops_per_token(**shapes) -> float:
    """Forward plus backward: the backward pass computes two products (with
    respect to the input and to the weight) for each of the forward's."""
    return 3.0 * forward_flops_per_token(**shapes)


def shapes_of(model_cfg) -> dict:
    """The arguments above from the program's ModelConfig."""
    return dict(layers=model_cfg.num_layers, hidden=model_cfg.hidden_size,
                heads=model_cfg.num_attention_heads,
                kv_heads=model_cfg.num_kv_heads,
                head_dim=model_cfg.kv_channels,
                ffn=model_cfg.ffn_hidden_size,
                vocab=model_cfg.padded_vocab_size,
                seq=model_cfg.seq_length, glu=bool(model_cfg.is_glu))
