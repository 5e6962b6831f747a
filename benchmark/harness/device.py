"""The look for the chip, and what the result line says about it."""
from __future__ import annotations


class NoChip(RuntimeError):
    pass


def require_chips(chips: int):
    """The devices this cell runs on.  Raises unless JAX's default backend
    is a TPU with at least `chips` devices: nothing falls back to a CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default backend is {devs[0].platform!r} "
                     f"({devs[0].device_kind}, {len(devs)} device(s)), not a "
                     "TPU: nothing was measured")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX reports "
                     f"{len(devs)}: nothing was measured")
    return devs[:chips]


def temp_bytes(compiled) -> int:
    """Temporaries of one compiled program on one device, as its compiler
    reports them; 0 where it does not."""
    try:
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except Exception:
        return 0


def describe(devices, program_temp_bytes: int = 0) -> dict:
    """`memory_peak_bytes` is the peak on the fullest chip while the timed
    program runs.  On this TPU the allocator's `peak_bytes_in_use` counts
    live arrays only: an executable's temporaries are laid out by the
    compiler and reserved apart (a BERT step over 256 rows leaves it at the
    weights and Adam's moments; the compiler's own out-of-memory report
    adds "program" to "arguments").  So the peak is the larger of the
    allocator's peak and live bytes + the timed program's temporaries."""
    import jax
    import sys
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"info memory_stats {d.id} {stats} program_temp_bytes="
              f"{program_temp_bytes}", file=sys.stderr)
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("bytes_in_use", 0)) + program_temp_bytes)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class CompileWatch:
    """Counts JAX's own compile events (persistent-cache hits and misses):
    what set-up compiled or loaded, and that the window did neither."""

    def __init__(self):
        from jax import monitoring
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, *a, **kw):
        name = str(name)
        if "/compilation_cache/" not in name:
            return
        if "cache_miss" in name:
            self.misses += 1
        elif "cache_hit" in name:
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.hits, self.misses
