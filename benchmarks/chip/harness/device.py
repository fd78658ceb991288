"""The chip a run measures: it must be there, and every result names it."""
from __future__ import annotations


class NoChip(RuntimeError):
    pass


def gate(chips: int):
    """The accelerator devices, or NoChip when JAX finds no TPU or fewer
    chips than the cell asks for. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def enable_compile_cache() -> str:
    """The system's persistent compilation cache (JAX_COMPILATION_CACHE_DIR,
    else `.jax_cache/` in the checkout), keeping every program: the eager
    ops of the serving path each compile in well under JAX's default 1 s
    threshold, and a run would otherwise compile them all again."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where
