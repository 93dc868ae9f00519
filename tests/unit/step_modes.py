"""The ways a ``RaggedInferenceEngine`` can be told to take a step, as
``RaggedConfig`` overrides. Every feature that must be token-identical
whatever the path (prefix cache, KV tiers, low-bit KV, hand-off, cancel)
parametrises over the ones that make sense for it."""

MODES = {
    "plain": {},                         # the device step, per-token prefill
    "tiled": {"prefill_tile": 8},        # the device step every cell runs
    "sched": {"sched_steps": 4},         # K decode steps in one program
    # the host-staged fallback: what a degraded engine serves on (rungs 1
    # and 2 of the watchdog's ladder, tiled and not)
    "host": {"device_state": False},
    "host_tiled": {"device_state": False, "prefill_tile": 8},
}
