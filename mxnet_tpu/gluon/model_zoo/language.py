"""Language-model zoo: decoders built from ``gluon.nn``'s sequence layers.

The reference's zoo is vision only.  A constructor here takes every
width as an argument (the defaults are the published ones) and builds
the net that ``parallel.make_train_step`` trains like any other: token
ids ``(batch, length)`` in, logits ``(batch, length, vocab)`` out.
"""
from __future__ import annotations

from .. import nn

__all__ = ["NemotronH", "nemotron_h"]


class NemotronH(nn.HybridStack):
    """The ``nemotron_h`` family's decoder (Nemotron-H, arXiv:2504.03624):
    pre-norm residual layers chosen by a pattern string, ``M`` a Mamba-2
    mixer, ``E`` a sigmoid-routed mixture of squared-ReLU experts beside
    a shared expert, ``*`` grouped-query attention without a positional
    embedding (the Mamba layers give order), ``-`` a squared-ReLU MLP."""


def nemotron_h(vocab_size=131072, hidden_size=2688,
               pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
               mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
               ssm_state_size=128, conv_kernel=4, chunk_size=128,
               num_attention_heads=32, num_key_value_heads=2, head_dim=128,
               n_routed_experts=128, num_experts_per_tok=6,
               moe_intermediate_size=1856,
               moe_shared_expert_intermediate_size=3712,
               routed_scaling_factor=2.5, intermediate_size=1856,
               layer_norm_epsilon=1e-5, experts_held=None, remat=True,
               **kwargs):
    """NVIDIA-Nemotron-3-Nano-30B-A3B's architecture by default; every
    width is an argument.  ``experts_held`` is the ``(first, end)`` run of
    expert ids this chip holds of each mixture (all of them by default):
    the router still scores all ``n_routed_experts``.  ``vocab_size`` may
    be a chip's slice of the vocabulary, ``pattern`` its layers."""
    held = range(*experts_held) if experts_held is not None else None
    eps = layer_norm_epsilon
    mixers = {
        "M": lambda: nn.Mamba2Mixer(
            hidden_size, mamba_num_heads, mamba_head_dim, n_groups,
            ssm_state_size, conv_kernel, chunk_size, eps),
        "E": lambda: nn.SparseMoE(
            hidden_size, n_routed_experts, num_experts_per_tok,
            moe_intermediate_size, moe_shared_expert_intermediate_size,
            held, routed_scaling_factor),
        "*": lambda: nn.GQAttention(
            hidden_size, num_attention_heads, num_key_value_heads, head_dim),
        "-": lambda: nn.SquaredReLUMLP(hidden_size, intermediate_size),
    }
    return NemotronH(vocab_size, hidden_size, pattern, mixers, eps,
                     remat=remat, **kwargs)
