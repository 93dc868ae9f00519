"""Runtime probing of optional XLA_FLAGS.

Every library that links XLA (jaxlib, libtpu) parses ``XLA_FLAGS`` with its
*own* flag registry, and ``parse_flags_from_env.cc`` F-aborts the whole
process on any flag unknown to one of them — so a flag one of them takes can
still be fatal to a process that loads the other.  The safe way to use an
optional flag is to probe it in a throwaway subprocess and adopt only what
survives.

Mirrors the capability-probe philosophy of the reference's accelerator
selection (``/root/reference/accelerator/real_accelerator.py:51``) applied to
XLA flags instead of device backends.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile

_PROBE_SNIPPET = (
    "import jax; jax.config.update('jax_platforms', 'cpu'); jax.devices()"
)

# parse_flags_from_env.cc's F-abort message — the one *definitive* rejection
# signal.  Anything else (timeout, import crash, OSError) may be transient and
# must not be cached.
_REJECT_MARKER = b"Unknown flag"


def _cache_path(key: str) -> str:
    return os.path.join(
        tempfile.gettempdir(), f"dstpu_xla_flag_probe_{key}.json"
    )


def probe_extra_xla_flags(
    candidates: list[str],
    base_flags: str = "",
    timeout: float = 120.0,
    use_cache: bool = True,
    env_overrides: dict[str, str | None] | None = None,
    keep_transient: bool = False,
) -> list[str]:
    """Return the subset of ``candidates`` this environment's XLA flag parsers accept.

    Spawns ``python -c "import jax; jax.devices()"`` with
    ``XLA_FLAGS = base_flags + candidates``; on a clean exit all candidates are
    adopted.  Candidates already present in ``base_flags`` are skipped (the
    caller/user set them explicitly — don't second-guess or duplicate them).
    Only *definitive* verdicts are cached on disk: a clean exit, or a child
    that died printing ``Unknown flag``.  Transient failures (timeout, import
    crash) adopt nothing but leave the cache alone so the next run re-probes.

    ``env_overrides`` lets the caller make the probe child's environment match
    the real child it is probing on behalf of (value ``None`` = unset).

    ``keep_transient`` flips the default-deny stance for transient verdicts:
    candidates whose probe fails *indeterminately* (timeout, import crash) are
    adopted instead of dropped.  Use it when the candidates were already in
    the environment — there, dropping on a flaky probe silently changes the
    user's configuration, so only a definitive ``Unknown flag`` rejection may
    remove a flag.  Transient verdicts are never cached either way, so the
    cache stays verdict-pure and shared across both stances.
    """
    base_names = {f.split("=", 1)[0] for f in base_flags.split()}
    candidates = [
        c for c in candidates if c and c.split("=", 1)[0] not in base_names
    ]
    if not candidates:
        return []

    try:
        import jax

        jax_ver = jax.__version__
    except Exception:  # pragma: no cover - jax is a hard dep everywhere else
        jax_ver = "unknown"

    # Key on what determines acceptance: the candidate set, the flag-parser
    # registries in play (proxied by interpreter + jax version), and the env
    # overrides (they change which PJRT plugins load, hence which registries
    # parse the flags).  base_flags is deliberately excluded — acceptance of a
    # flag doesn't depend on which other valid flags accompany it, and
    # including it would fragment the cache across e.g. different
    # --xla_force_host_platform_device_count values.
    # env vars that change which PJRT plugins (and hence flag registries) load
    plugin_env = {
        k: os.environ.get(k)
        for k in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "LD_PRELOAD",
                  "TPU_LIBRARY_PATH", "TPU_NAME", "PJRT_DEVICE")
    }
    key_src = json.dumps(
        [sorted(candidates), sys.executable, jax_ver,
         sorted(plugin_env.items(), key=str),
         sorted((env_overrides or {}).items(), key=str)]
    )
    key = hashlib.sha256(key_src.encode()).hexdigest()[:16]
    cache = _cache_path(key)
    if use_cache and os.path.exists(cache):
        try:
            with open(cache) as f:
                return json.load(f)
        except (json.JSONDecodeError, OSError):
            pass

    def _probe(flags: list[str]) -> str:
        """-> 'ok' | 'rejected' | 'transient'"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (base_flags + " " + " ".join(flags)).strip()
        env.pop("PYTEST_CURRENT_TEST", None)
        for k, v in (env_overrides or {}).items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _PROBE_SNIPPET],
                env=env,
                capture_output=True,
                timeout=timeout,
            )
        except (subprocess.TimeoutExpired, OSError):
            return "transient"
        if proc.returncode == 0:
            return "ok"
        if _REJECT_MARKER in proc.stderr or _REJECT_MARKER in proc.stdout:
            return "rejected"
        return "transient"

    verdict = _probe(candidates)
    definitive = verdict != "transient"
    # A transient batch verdict can hide a definitively-bad flag; under
    # keep_transient that flag would otherwise ride through and kill the real
    # child, so bisect on transient batches too, not just rejected ones.
    bisect = len(candidates) > 1 and (
        verdict == "rejected" or (verdict == "transient" and keep_transient)
    )
    if verdict == "ok":
        accepted = list(candidates)
    elif bisect:
        accepted = []
        for c in candidates:
            v = _probe([c])
            if v == "ok":
                accepted.append(c)
            elif v == "transient":
                definitive = False
                if keep_transient:
                    accepted.append(c)
    elif verdict == "transient" and keep_transient:
        accepted = list(candidates)
    else:
        accepted = []

    if use_cache and definitive:
        try:
            with open(cache, "w") as f:
                json.dump(accepted, f)
        except OSError:
            pass
    return accepted


# --xla_<platform>_* flags register only when that platform's backend links
# in, so a child forced onto a different platform F-aborts on them before
# any probe could help.  Used by sanitize_xla_flags to pre-drop statically.
_PLATFORM_PREFIXES = {"cpu": "--xla_cpu", "gpu": "--xla_gpu",
                      "tpu": "--xla_tpu"}


def sanitize_xla_flags(
    flags: str,
    target_platform: str = "cpu",
    timeout: float = 120.0,
    use_cache: bool = True,
    env_overrides: dict[str, str | None] | None = None,
) -> str:
    """Filter an *inherited* ``XLA_FLAGS`` string down to what a child forced
    onto ``target_platform`` can actually parse.

    The failure this guards against: a parent running under TPU (or a stale
    probe cache) leaves platform-specific flags in the environment; a
    subprocess spawned with ``JAX_PLATFORMS=cpu`` then dies in
    ``parse_flags_from_env.cc`` with ``Unknown flag in XLA_FLAGS: ...``
    before running a single line of user code.

    Two passes.  Flags carrying another platform's name prefix
    (``--xla_tpu*`` when forcing CPU, and so on) are dropped statically — the
    target backend never registers them, and probing each costs a subprocess.
    The survivors are then probed in the child's environment
    (``env_overrides`` should match the real child) with
    ``keep_transient=True``: these flags were already in the environment, so
    only a definitive ``Unknown flag`` rejection removes one; flaky probes
    keep it.  Order is preserved.  Returns the sanitized flag string.
    """
    toks = [t for t in flags.split() if t]
    if not toks:
        return ""
    wrong = tuple(p for plat, p in _PLATFORM_PREFIXES.items()
                  if plat != target_platform)
    survivors = [t for t in toks if not t.startswith(wrong)]
    if not survivors:
        return ""
    kept = set(probe_extra_xla_flags(
        survivors, timeout=timeout, use_cache=use_cache,
        env_overrides=env_overrides, keep_transient=True,
    ))
    return " ".join(t for t in survivors if t in kept)
