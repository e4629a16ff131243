"""The witness's net: the zoo's own layers, put together through gluon's
public interface.  No model: it is there so that a configuration whose
input is token ids has something to build (tests/chipbench/witness)."""


def token_mlp(vocab, width, hidden):
    """``Embedding`` -> ``Dense`` + ReLU -> ``Dense`` to the vocabulary,
    each position of a sequence on its own."""
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential(prefix="tokwit_")
    with net.name_scope():
        net.add(nn.Embedding(vocab, width),
                nn.Dense(hidden, activation="relu", flatten=False),
                nn.Dense(vocab, flatten=False))
    return net
