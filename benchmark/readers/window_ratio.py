"""One of the window's counts over another, scaled: for example the
window's seconds over the reverse steps run in it."""


def read(ctx, numerator, denominator, scale=1.0):
    w = ctx["window"]
    if not w.get(denominator) or numerator not in w:
        return None
    return scale * w[numerator] / w[denominator]
