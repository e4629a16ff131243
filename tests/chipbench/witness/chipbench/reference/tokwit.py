"""The witness's plain reference: a table of embeddings, a dense layer
with a ReLU and a dense layer to the vocabulary, each position of a
sequence on its own; float32 ``jax.numpy``.  The state is a flat list in
the order the layers are applied; the forward pass moves none of it."""
import jax

from chipbench import refmath as rm


def param_specs(arch, vocab, classes):
    """[(kind, shape)] of every array of the net's state."""
    return [("embedding", (vocab, arch["width"])),
            ("dense", (arch["hidden"], arch["width"])),
            ("bias", (arch["hidden"],)),
            ("dense", (classes, arch["hidden"])),
            ("bias", (classes,))]


def forward(params, x, arch, precision="float32"):
    """``(logits, moved)`` of a batch of token ids ``x`` (N, seq)."""
    table, w1, b1, w2, b2 = params
    h = table[x.astype("int32")]
    h = jax.nn.relu(rm.dense(h, w1, b1, precision))
    return rm.dense(h, w2, b2, precision), {}
