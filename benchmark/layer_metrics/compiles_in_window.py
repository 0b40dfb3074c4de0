"""Device planes (mat/device_plane.py): programs first seen inside the
window — the kernel layer's compile misses plus what JAX itself reports
compiling or loading.  Should read 0; each one stalls the reads behind
it, so it moves ``read_p95_ms``."""


def read(w):
    return float(w.counters["kernel_compile_misses"]
                 + w.counters["jax_programs_compiled"])
