"""``model.step_roofline_kv`` for a latent cache: the same formula with the
row bytes and the pair FLOPs of the cell's reference module
(``latent_spans.step_roofline_kv``)."""
from latent_spans import step_roofline_kv as read  # noqa: F401
