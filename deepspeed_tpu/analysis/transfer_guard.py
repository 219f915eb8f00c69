"""Dynamic counterpart of DST001: transfer-guard sanitizer.

The static rule says "no host-transfer-shaped call on a hot path unless
justified"; this module proves the same claim at RUNTIME with jax's
transfer guards.  The contract the serving hot paths now follow:

- every INTENDED device->host fetch is **explicit** (`jax.device_get`,
  carrying a `# dstpu: noqa[DST001] reason`), and every intended
  host->device staging goes through `jnp.asarray`/`jax.device_put`
  (also explicit per jax's guard semantics);
- therefore running the hot path under ``jax.transfer_guard_*
  ("disallow")`` — which permits explicit transfers and raises on
  implicit ones — turns ANY accidental materialization into a loud
  error at the exact offending call.

Bonus teeth: an un-bucketed shape hitting the decode path mid-serve
recompiles its program, and the fresh trace transfers new constants —
implicit host->device transfers the guard catches.  The sanitizer is
thereby also a dynamic recompile detector (DST004's runtime analog).

Platform caveat (measured): the CPU
backend shares memory with the host, so device->host reads are
zero-copy and never trip the guard — d2h enforcement only has teeth on
a real accelerator.  Host->device enforcement fires everywhere,
including CPU, which is what the tier-1 burst-decode test leans on.
`ServingConfig.transfer_guard` wires this into `ServeLoop.step`.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

__all__ = ["GUARD_LEVELS", "no_host_transfers", "serve_guard"]

# levels accepted by jax.transfer_guard_* (plus our "off" sentinel)
GUARD_LEVELS = ("off", "allow", "log", "disallow", "log_explicit",
                "disallow_explicit")


def _check(level: Optional[str], name: str) -> Optional[str]:
    if level is None or level == "off":
        return None
    if level not in GUARD_LEVELS:
        raise ValueError(
            f"{name}={level!r}: expected one of {GUARD_LEVELS}")
    return level


@contextlib.contextmanager
def no_host_transfers(device_to_host: Optional[str] = "disallow",
                      host_to_device: Optional[str] = None,
                      device_to_device: Optional[str] = None
                      ) -> Iterator[None]:
    """Scope in which implicit transfers in the given directions raise.

    Defaults guard only device->host — the host-sync direction DST001 is
    about.  Pass ``host_to_device="disallow"`` too for the full
    sanitizer (only after warm-up: tracing/compilation legitimately
    embeds host constants, so compile inside the guard trips it — which
    is exactly the recompile-detection feature, but means the FIRST call
    of each program must happen outside or the test must expect it).
    """
    import jax
    d2h = _check(device_to_host, "device_to_host")
    h2d = _check(host_to_device, "host_to_device")
    d2d = _check(device_to_device, "device_to_device")
    with contextlib.ExitStack() as stack:
        if d2h is not None:
            stack.enter_context(jax.transfer_guard_device_to_host(d2h))
        if h2d is not None:
            stack.enter_context(jax.transfer_guard_host_to_device(h2d))
        if d2d is not None:
            stack.enter_context(jax.transfer_guard_device_to_device(d2d))
        yield


def serve_guard(level: str):
    """Guard factory for `ServeLoop.step` (`ServingConfig.transfer_guard`):
    "off" -> no-op context, "log"/"disallow" -> device->host guard at
    that level around every serve step.  Host->device stays open — the
    serve loop legitimately stages fresh prompt/table buffers each step;
    the staging calls are explicit (`jnp.asarray`) anyway, but prefill
    admission also compiles new shape buckets on first sight, and a
    production guard must not make the first long prompt crash."""
    if level not in ("off", "log", "disallow"):
        raise ValueError(
            f"serving.transfer_guard={level!r}: expected 'off', 'log' or "
            f"'disallow'")
    if level == "off":
        return contextlib.nullcontext
    return lambda: no_host_transfers(device_to_host=level)
