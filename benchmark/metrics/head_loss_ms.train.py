"""Device time a step in the head and the loss: the events under the
``head_loss`` scope (final LayerNorm or MLM transform, logits, softmax,
loss reduction and their backward, the tied head's weight gradient), in
milliseconds."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.device_ms(run, "head_loss")
