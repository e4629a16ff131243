"""All images of all steps completed in the window over the window's
whole wall time (first dispatch to the last step's loss on the host),
across all the cell's chips."""


def read(run):
    w = run["window"]
    return w["images"] / w["seconds"]
