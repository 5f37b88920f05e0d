"""decode_tok_s: answer tokens appended and attended through the layer
tier over the whole window, divided by the window (host clock)."""


def read(run):
    return run.rec.tokens / run.window_s
