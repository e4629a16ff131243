"""All rows of the batch of all steps completed in the window over the
window's whole wall time (first dispatch to the last step's loss on the
host), across all the cell's chips.  A row is an image or, under token
input, a sequence: the rate then reads sequences a second (the result
carries a step's ``tokens`` beside its ``batch``)."""


def read(run):
    w = run["window"]
    return w["images"] / w["seconds"]
