"""Counters both kinds of job read from the program and the device."""
import jax


def compile_count():
    """Compilations so far: the program's own record of its step
    programs, and every request JAX made of its compilation cache (a
    small eager operation's first use is one, hit or miss).  Unchanged
    over a window means nothing compiled inside it."""
    from mxnet_tpu import compile_cache, profiler

    return len(profiler.compile_events()) \
        + compile_cache.cache_stats()["requests"]


def peak_bytes():
    """``peak_bytes_in_use`` on the fullest chip: the process's lifetime
    peak, so read it before the reference runs."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
