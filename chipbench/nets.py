"""Nets that a configuration's ``constructor`` can name beside the
program's model zoo: the zoo's own layers, put together through gluon's
public interface, where the zoo's function has no argument for what the
configuration states."""


def vgg_without_dropout(num_layers, **kwargs):
    """The zoo's VGG with its two Dropout layers left out (the
    configuration's ``dropout`` 0): every other layer, with its name and
    in its order, in one sequence."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo import vision

    zoo = vision.get_vgg(num_layers, **kwargs)
    net = nn.HybridSequential(prefix="")
    for layer in zoo.features:
        if not isinstance(layer, nn.Dropout):
            net.add(layer)
    net.add(zoo.output)
    return net
