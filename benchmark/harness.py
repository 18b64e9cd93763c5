"""What every traffic kind needs from the harness: the device check, the
program's configuration object built from a configuration file, seeded
weights placed on the cell's chips, the parts of set-up, the compile
listener and the profiler window.  Nothing here knows a cell's or a
configuration's name."""

import dataclasses
import importlib
import shutil
import sys
import tempfile
import time

import peaks


def say(tag: str, **fields) -> None:
    """An earlier line of a run: ``tag: key=value ...`` (never the last line)."""
    print(tag + ": " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def load_symbol(path: str):
    """``package.module:attr`` -> the object; the configuration file names
    the program's classes, so a new family needs no edit here."""
    module, attr = path.split(":")
    return getattr(importlib.import_module(module), attr)


class SetupParts:
    """Seconds of each part of set-up, printed on an earlier line of every run."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self._last = t_start
        self.parts = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._last
        self._last = now

    def report(self, t_open: float, **more) -> float:
        """``setup_s`` up to the window's opening at ``t_open``; ``more`` are
        parts that lie ahead (the lead-in), the rest is ``other``."""
        setup_s = t_open - self.t_start
        parts = {**self.parts, **more}
        parts["other"] = setup_s - sum(parts.values())
        say("setup_parts", **{k: round(v, 3) for k, v in parts.items()}, setup_s=round(setup_s, 3))
        return setup_s


def open_device(chips: int, rehearse: bool) -> dict:
    """Find the cell's chips or exit non-zero.  A rehearsal (never a run the
    driver makes) uses virtual CPU devices and reports no device metric."""
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(chips, 1))
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": chips}
    if not rehearse:
        if devices[0].platform != "tpu":
            sys.exit(f"benchmark: no TPU found, JAX reports {info}; nothing was run")
        peaks.match_device_kind(info["kind"])  # an unknown kind raises: no default peak
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips, JAX sees {len(devices)}")
    # the program places the cache (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
    from deepspeed_tpu.utils import compile_cache
    say("compile_cache", dir=compile_cache.enable())
    return info


def program_config(cfg: dict):
    """The program's own configuration object: the published keys it knows
    by the same name, plus the ``program.fields`` the file states."""
    import jax.numpy as jnp
    cls = load_symbol(cfg["program"]["config"])
    names = {f.name for f in dataclasses.fields(cls)}
    fields = {k: v for k, v in cfg.items() if k in names}
    for k, v in cfg["program"]["fields"].items():
        fields[k] = getattr(jnp, v) if k.endswith("dtype") else v
    return cls(**fields)


def seeded_params(cfg: dict, pcfg, seed: int, devices, shardings=None):
    """bfloat16 weights from the seed, in the program's parameter tree
    (shapes from ``eval_shape`` of its ``init``; values from weights.py).
    ``shardings(abstract boxed tree)`` gives the layout to make them in;
    without it each leaf is split over ``devices`` along its first divisible
    axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import weights
    model = load_symbol(cfg["program"]["model"])(pcfg)
    boxed = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    abstract = nn.meta.unbox(boxed)
    if shardings is not None:
        return model, weights.make_params(abstract, seed, out_shardings=shardings(boxed))
    mesh = Mesh(np.asarray(devices), ("w", ))

    def place(leaf):
        axis = next((i for i, n in enumerate(leaf.shape) if n % len(devices) == 0 and n >= len(devices)), None)
        spec = [None] * len(leaf.shape)
        if axis is not None and len(devices) > 1:
            spec[axis] = "w"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return model, weights.make_params(abstract, seed, out_shardings=jax.tree.map(place, abstract))


def n_params(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


class CompileListener:
    """Counts JAX backend compiles; ``since(t)`` is the count after time t."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if name.endswith("backend_compile_duration"):
            self.times.append(time.monotonic())

    def since(self, t: float) -> int:
        return sum(1 for x in self.times if x >= t)


class TraceWindow:
    """The profiler over the last ``trace_s`` seconds of the measured window;
    the trace is reduced and its files removed."""

    def __init__(self, ctx: dict, t_open: float, seconds: float):
        self.enabled = ctx["trace"]
        self.rehearse = ctx["rehearse"]
        self.t_start = t_open + max(seconds - min(4.0, seconds / 2.0), 0.0)
        self.t_stop = t_open + seconds
        self.dir = None
        self.started = self.stopped = None
        self.window = None   # (start, stop) on the caller's clock
        self.reduced = None

    def poll(self, now: float) -> None:
        """Call between units of work (ticks, steps)."""
        import jax
        if not self.enabled:
            return
        if self.started is None and now >= self.t_start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = now
        elif self.started is not None and self.stopped is None and now >= self.t_stop:
            self.stop(now)

    def stop(self, now: float) -> None:
        import jax

        import trace_reduce
        if not self.enabled or self.started is None or self.stopped is not None:
            return
        self.stopped = now
        self.window = (self.started, now)
        jax.profiler.stop_trace()
        try:
            path = trace_reduce.find_xplane(self.dir)
            if not self.rehearse:  # a CPU rehearsal has no device plane to reduce
                self.reduced = trace_reduce.reduce(trace_reduce.load(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span of the benchmark's own, written into the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def hbm_bytes(devices, key: str = "peak_bytes_in_use") -> list:
    return [int((d.memory_stats() or {}).get(key, 0)) for d in devices]
