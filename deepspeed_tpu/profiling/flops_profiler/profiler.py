"""FLOPs profiler — XLA-native cost accounting.

TPU-native analog of ``deepspeed/profiling/flops_profiler/profiler.py:30
FlopsProfiler`` (~1,300 LoC).  The reference monkey-patches ~50 torch
functional ops to count MACs as eager calls happen; under XLA the compiler
already knows the exact op-level cost of the compiled program, so:

* whole-program numbers come from ``Compiled.cost_analysis()`` (flops,
  bytes accessed, peak memory) on the jitted step — exact, fusion-aware,
  zero overhead;
* the per-module table comes from ``flax.linen.tabulate(compute_flops=
  True, compute_vjp_flops=True)`` which costs each submodule's forward
  and backward separately;
* wall-clock per step comes from the engine timers.

Same public surface: ``start_profile / stop_profile / reset_profile /
end_profile / get_total_flops / get_total_macs / get_total_duration /
get_total_params / print_model_profile`` and the standalone
``get_model_profile(model, input_shape)``.
"""

import time
from typing import Any, Optional

import jax
import numpy as np


# ------------------------------------------------------------------ helpers


def number_to_string(num, units=None, precision=2):
    if units is None:
        if num >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if num >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if num >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if num >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}[units]
    return f"{num / scale:.{precision}f} {units}"


def flops_to_string(flops, units=None, precision=2):
    return number_to_string(flops, units=units, precision=precision) + "FLOPS"


def macs_to_string(macs, units=None, precision=2):
    return number_to_string(macs, units=units, precision=precision) + "MACs"


def params_to_string(params_num, units=None, precision=2):
    return number_to_string(params_num, units=units, precision=precision).strip()


def duration_to_string(duration, units=None, precision=2):
    if duration > 1:
        return f"{duration:.{precision}f} s"
    if duration > 1e-3:
        return f"{duration * 1e3:.{precision}f} ms"
    return f"{duration * 1e6:.{precision}f} us"


def xla_cost_analysis(fn, *args, **kwargs):
    """Compile ``fn`` and return XLA's cost analysis dict:
    ``{'flops': .., 'bytes accessed': .., ...}`` (exact, post-fusion)."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    return dict(compiled.cost_analysis() or {})


# ------------------------------------------------------------------ profiler


class FlopsProfiler:
    """ref: flops_profiler/profiler.py:30.

    ``model`` is a flax module; ``ds_engine`` the DeepSpeedEngine (optional).
    When attached to an engine, profiles the engine's compiled train step;
    standalone, profiles ``model.apply`` on the example batch passed to
    ``start_profile``.
    """

    def __init__(self, model=None, ds_engine=None, recompute_fwd_factor=0.0):
        self.model = model
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self.metrics_registry = None
        self.reset_profile()

    def attach_metrics(self, registry) -> "FlopsProfiler":
        """Publish each profile's numbers into a telemetry
        ``MetricsRegistry`` (docs/OBSERVABILITY.md): gauges
        ``profiler/flops_per_step``, ``profiler/macs_per_step``,
        ``profiler/params``, ``profiler/bytes_per_step`` and
        ``profiler/step_duration_s`` are set every time ``stop_profile``
        collects — the bridge from the one-shot profile printout to the
        always-on metrics surface."""
        self.metrics_registry = registry
        return self

    # -- lifecycle (ref: profiler.py:74 start_profile / :134 stop / :203 end)

    def start_profile(self, ignore_list=None, example_batch=None):
        self.reset_profile()
        self.started = True
        self._t0 = time.perf_counter()  # dslint-ok(determinism): flops profiler measures the real step wall duration it reports
        self._example_batch = example_batch

    def stop_profile(self):
        if not self.started:
            return
        self._duration = time.perf_counter() - self._t0  # dslint-ok(determinism): flops profiler measures the real step wall duration it reports
        self._collect()

    def reset_profile(self):
        self._duration = 0.0
        self._flops = 0
        self._macs = 0
        self._params = 0
        self._bytes = 0
        self._table = None
        self._example_batch = None

    def end_profile(self):
        self.started = False

    # -- collection

    def _engine_cost(self):
        eng = self.ds_engine
        if eng is None or eng._train_step_fn is None or eng.state is None:
            return None
        fn = eng._train_step_fn
        try:
            # lower() alone re-traces but skips the expensive XLA compile —
            # the executable for this (state, batch) signature is already in
            # jit's cache from the step that just ran
            ca = fn.lower(eng.state, self._example_batch).cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return dict(ca or {})
        except Exception:
            return None

    def _collect(self):
        ca = None
        if self.ds_engine is not None and self._example_batch is not None:
            ca = self._engine_cost()
        if ca is None and self.model is not None and self._example_batch is not None:
            model = self.model
            # init OUTSIDE the analyzed fn: parameter init flops must not
            # count toward the forward-pass cost
            variables = model.init(jax.random.PRNGKey(0), self._example_batch)

            def apply_fn(batch):
                return model.apply(variables, batch)

            try:
                ca = xla_cost_analysis(apply_fn, self._example_batch)
            except Exception:
                ca = {}
        ca = ca or {}
        self._flops = int(ca.get("flops", 0))
        self._macs = self._flops // 2  # 1 MAC = 2 flops on the MXU
        self._bytes = int(ca.get("bytes accessed", 0))
        if self.ds_engine is not None and self.ds_engine.state is not None:
            self._params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.ds_engine.state.params))
        if self.metrics_registry is not None:
            reg = self.metrics_registry
            reg.gauge("profiler/flops_per_step").set(self._flops)
            reg.gauge("profiler/macs_per_step").set(self._macs)
            reg.gauge("profiler/params").set(self._params)
            reg.gauge("profiler/bytes_per_step").set(self._bytes)
            reg.gauge("profiler/step_duration_s").set(self._duration)

    # -- getters (ref: profiler.py:232-279)

    def get_total_flops(self, as_string=False):
        return flops_to_string(self._flops) if as_string else self._flops

    def get_total_macs(self, as_string=False):
        return macs_to_string(self._macs) if as_string else self._macs

    def get_total_duration(self, as_string=False):
        return duration_to_string(self._duration) if as_string else self._duration

    def get_total_params(self, as_string=False):
        return params_to_string(self._params) if as_string else self._params

    def get_total_bytes(self, as_string=False):
        return number_to_string(self._bytes) + "B" if as_string else self._bytes

    # -- printing (ref: profiler.py:286 print_model_profile)

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1, detailed=True, output_file=None):
        import sys
        out = open(output_file, "w") if output_file else sys.stdout
        dur = self._duration or 1e-9
        print("\n-------------------------- DeepSpeed-TPU Flops Profiler --------------------------", file=out)
        print(f"Profile Summary at step {profile_step}:", file=out)
        print("Notations:\n"
              "data parallel size (dp_size), model parallel size(mp_size),\n"
              "number of parameters (params), number of multiply-accumulate operations(MACs),\n"
              "number of floating-point operations (flops), floating-point operations per second (FLOPS)",
              file=out)
        if self.ds_engine is not None:
            print(f"dp/world size:                                          {jax.device_count()}", file=out)
        print(f"params:                                                 {self.get_total_params(True)}", file=out)
        print(f"fwd+bwd MACs per step:                                  {self.get_total_macs(True)}", file=out)
        print(f"fwd+bwd flops per step:                                 {self.get_total_flops(True)}", file=out)
        print(f"HBM bytes accessed per step:                            {self.get_total_bytes(True)}", file=out)
        print(f"step latency:                                           {self.get_total_duration(True)}", file=out)
        print(f"achieved FLOPS:                                         {flops_to_string(self._flops / dur)}", file=out)
        if detailed and self._table:
            print(self._table, file=out)
        print("-----------------------------------------------------------------------------------", file=out)
        if output_file:
            out.close()

    def print_model_aggregated_profile(self, module_depth=-1, top_modules=1):
        self.print_model_profile(module_depth=module_depth, top_modules=top_modules, detailed=False)


# -------------------------------------------------------- standalone profile


def get_model_profile(model,
                      input_shape=None,
                      args=(),
                      kwargs=None,
                      print_profile=True,
                      detailed=True,
                      module_depth=-1,
                      top_modules=1,
                      warm_up=1,
                      as_string=True,
                      output_file=None,
                      ignore_modules=None,
                      mode='forward',
                      rngs=None):
    """Profile a flax model (ref: profiler.py get_model_profile): returns
    (flops, macs, params).  Per-module breakdown via ``nn.tabulate`` with
    flops costing; whole-program totals from XLA cost analysis.
    """
    import jax.numpy as jnp
    from flax import linen as nn

    kwargs = kwargs or {}
    if input_shape is not None:
        assert isinstance(input_shape, (tuple, list)), "input_shape must be a tuple/list"
        args = (jnp.ones(input_shape, jnp.int32), )

    rng = rngs if rngs is not None else jax.random.PRNGKey(0)

    # totals: compile fwd (and optionally bwd) and read XLA's numbers
    variables = jax.eval_shape(lambda: model.init(rng, *args, **kwargs))
    params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(variables))

    def fwd(v, *a):
        return model.apply(v, *a, **kwargs)

    concrete_vars = model.init(rng, *args, **kwargs)
    ca = xla_cost_analysis(fwd, concrete_vars, *args)
    flops = int(ca.get("flops", 0))

    if mode == 'generate' or mode == 'forward':
        pass
    elif mode == 'train':
        def train_fwd_bwd(v, *a):
            def loss(vv):
                out = model.apply(vv, *a, **kwargs)
                leaf = out[0] if isinstance(out, (tuple, list)) else out
                return jnp.sum(leaf.astype(jnp.float32))
            return jax.grad(loss)(v)
        ca = xla_cost_analysis(train_fwd_bwd, concrete_vars, *args)
        flops = int(ca.get("flops", 0))
    macs = flops // 2

    table = None
    if detailed:
        try:
            tab_fn = nn.tabulate(model, rng, compute_flops=True, compute_vjp_flops=(mode == 'train'),
                                 depth=None if module_depth < 0 else module_depth)
            table = tab_fn(*args, **kwargs)
        except Exception:
            table = None

    if print_profile:
        import sys
        out = open(output_file, "w") if output_file else sys.stdout
        print(f"params: {params_to_string(params)}  flops: {flops_to_string(flops)}  "
              f"macs: {macs_to_string(macs)}", file=out)
        if table:
            print(table, file=out)
        if output_file:
            out.close()

    if as_string:
        return flops_to_string(flops), macs_to_string(macs), params_to_string(params)
    return flops, macs, params
