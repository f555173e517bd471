"""What every cell shares: the checkout's paths, the compile cache, the
look for a chip, the compile counter and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def load_module(*rel):
    """Import a file under the benchmark's directory by its path, so that
    file names may hold the `.` and `-` of metric and configuration
    names."""
    path = os.path.join(BENCH, *rel)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(rel).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> str:
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR` where that is
    set, else at the fixed `<checkout>/.jax_cache`; every program is kept,
    however short its compile, so that a second run compiles nothing."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(CHECKOUT, ".jax_cache")
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(chips: int):
    """The devices to run on; exit non-zero, naming what was found, where
    that is not a TPU with at least `chips` chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"bench: needs a TPU, found platform {platform!r} "
            f"({devices[0].device_kind}); no result")
        sys.exit(3)
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, found {len(devices)}; "
            "no result")
        sys.exit(3)
    return devices[:chips]


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts backend compiles (cache hits included, as XLA programs
    loaded) while armed; the window should see none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0

        def listener(event, duration, **_):
            if self.armed and event in self.EVENTS:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def check_line(numbers: dict, limits: dict) -> dict:
    """{name: {"value": v, "limit": l}} for every number compared."""
    return {k: {"value": numbers.get(k), "limit": limits[k]}
            for k in limits}


def is_correct(numbers: dict, limits: dict) -> bool:
    for k, lim in limits.items():
        v = numbers.get(k)
        if v is None or not math.isfinite(v) or v > lim:
            return False
    return True


def emit(result: dict) -> None:
    """The last lines: the numbers compared on stderr, then the result as
    the last line of stdout, its `check` key last."""
    for k, v in result.get("check", {}).items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
