"""The device check, jax's compile counters and the device's memory peak."""
from __future__ import annotations


class NoDevice(SystemExit):
    """JAX found no TPU, or not as many chips as the cell asks for."""


def require_tpu(chips: int):
    """Exactly ``chips`` TPU devices, or exit non-zero before any work;
    never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"chipbench: jax found no TPU (platform "
                       f"{devs[0].platform!r}); refusing to run on the CPU")
    if len(devs) != chips:
        raise NoDevice(f"chipbench: the cell asks for {chips} chips but "
                       f"jax sees {len(devs)} devices")
    return devs


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts of jax persistent-cache hits (a program loaded) and misses
    (a program compiled), in this process, from jax's monitoring events."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax
        self.counts = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **_kw):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def log(msg: str) -> None:
    print(msg, flush=True)
