"""The four ways a ``RaggedInferenceEngine`` can be told to take a step, as
``RaggedConfig`` overrides: the device step (``plain``, and ``tiled``, the
one every benchmark cell runs) and its host-staged fallback (``host_tiled``,
rung 1 of the watchdog's ladder: what an engine built ``tiled`` serves on
once degraded; ``host``, rung 2: the same with prefill tiles off, and rung
1 of an engine built ``plain``). Every feature that must be token-identical
whatever the path (prefix cache, KV tiers, low-bit KV, hand-off, cancel)
parametrises over the ones that make sense for it."""

MODES = {
    "plain": {},                         # the device step, per-token prefill
    "tiled": {"prefill_tile": 8},        # the device step every cell runs
    "host": {"device_state": False},
    "host_tiled": {"device_state": False, "prefill_tile": 8},
}
