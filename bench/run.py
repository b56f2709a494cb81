"""Benchmark harness: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each run builds the cell's configuration from the seed, warms up one chunk
of the cell's own shape (set-up), measures chunks of ``chunk_steps`` steps
until ``--seconds`` have passed, checks the window's first and last chunks
against the plain reference and the health counters, and prints one JSON line last on standard output.  With
``--trace 1`` the window runs under the profiler and the line carries the
cell's per-layer metrics instead of its end-to-end ones.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is a file found by name:

    bench/configs/<config>.json   sizes, source, assumed, reduced
    bench/configs/<config>.py     build(cfg, agents, seed) -> Simulation,
                                  kinds(cfg, key, n) -> each agent's kind
    bench/checks/<config>.py      reference(), compare(), LIMITS
    bench/traffic/<traffic>.json  components of the population generator
    bench/shapes/<shape>.py       draw() of one kind of component
    bench/workloads/<cell>.json   chunk_steps, start_step
    bench/metrics/<metric>.py     read(trace) -> number or None

Off a TPU (or with fewer chips than the cell asks for) the run exits 2 and
prints no result.  ``--agents N`` rehearses the whole run at N agents and the
configuration's density on any backend and exits 1 without the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """Everything a run of one cell reads from the benchmark's files."""

    name: str
    chips: int
    cfg: dict
    traffic: dict
    params: dict
    builder: object
    check: object
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, held: bool = False) -> "Cell":
        """The cell of ``BENCHMARK.json`` named ``name``; with ``held``, also
        one that is not listed there (yet), as ``<config>.<traffic>`` on one
        chip, from its files (calibration and tests only)."""
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None and held:
            config, traffic = name.split(".", 1)
            entry = {"name": name, "config": config, "traffic": traffic,
                     "chips": 1}
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        return cls.from_entry(entry, bench)

    @classmethod
    def from_entry(cls, entry: dict, bench: dict) -> "Cell":
        """The cell of one ``workloads`` entry, with the metrics of
        ``bench`` that apply to it."""
        name, config = entry["name"], entry["config"]

        def applies(metric):
            return name in metric.get("workloads", [name])

        return cls(
            name=name,
            chips=entry["chips"],
            cfg=read_json("configs", f"{config}.json"),
            traffic=read_json("traffic", f"{entry['traffic']}.json"),
            params=read_json("workloads", f"{name}.json"),
            builder=load_module(os.path.join(BENCH, "configs", f"{config}.py"),
                                f"config_{config}"),
            check=load_module(os.path.join(BENCH, "checks", f"{config}.py"),
                              f"check_{config}"),
            end_to_end=[m for m in bench["end_to_end"] if applies(m)],
            per_layer=[m for m in bench["per_layer"] if applies(m)],
        )


def scoped_scheduler(scheduler):
    """The scheduler with every op under a named scope of its own name, so
    the device trace can be attributed op by op."""
    import jax

    def wrap(name, fn):
        def run(ctx, state):
            with jax.named_scope(name):
                return fn(ctx, state)
        return run

    ops = tuple(dataclasses.replace(op, fn=wrap(op.name, op.fn))
                for op in scheduler.ops)
    return dataclasses.replace(scheduler, ops=ops)


class Target:
    """The system under test as the window drives it: the configuration's
    ``Simulation``, built for one chip or distributed over a mesh when the
    configuration names one (``mesh``, ``mesh_axes``; the builder module
    then also gives ``domain(cfg)`` and ``capacity(cfg)``)."""

    def __init__(self, cell: Cell, cfg: dict, seed: int):
        import jax.numpy as jnp

        import population

        sim = cell.builder.build(
            cfg, population.agents(cfg, cell.traffic, seed,
                                   cell.builder.kinds), seed)
        if "mesh" in cfg:
            from repro.core import distributed
            from repro.launch.mesh import make_mesh

            mesh = make_mesh(tuple(cfg["mesh"]), tuple(cfg["mesh_axes"]))
            self.dcfg = cell.builder.domain(cfg)
            d = sim.distribute(mesh, self.dcfg,
                               capacity=cell.builder.capacity(cfg))
            sched = scoped_scheduler(d.scheduler)
            self.sim = dataclasses.replace(
                d, scheduler=sched, step=distributed.make_distributed_step(
                    mesh, self.dcfg, d.config, scheduler=sched))
        else:
            self.dcfg = None
            built = sim.build()
            self.sim = dataclasses.replace(
                built, scheduler=scoped_scheduler(built.scheduler))
        st = self.sim.state
        self.state = dataclasses.replace(
            st, step=jnp.full_like(st.step, cell.params["start_step"]))
        self.op_freq = {op.name: op.frequency
                        for op in self.sim.scheduler.ops}
        self.grid_dims = self.sim.config.spec.dims
        self.resolution = {sub["name"]: sub["resolution"]
                           for sub in cfg.get("substances", [])}

    def run(self, state, n_steps: int):
        if self.dcfg is None:
            return self.sim.run_jit(n_steps, state=state)[0]
        return self.sim.run(n_steps, state=state)[0]

    def module_text(self, state, n_steps: int) -> str:
        """The optimized module text of the window's program (from the
        compilation cache), whose op metadata names each instruction's
        scope."""
        if self.dcfg is None:
            from repro.core import engine
            fn = engine.jitted_runner(self.sim.config, self.sim.scheduler)
            lowered = fn.lower(state, n_steps=n_steps, observables=None)
        else:
            lowered = self.sim.step.lower(state)
        return lowered.compile().as_text()

    def host_state(self, state) -> dict:
        """The state as host arrays in slot order; a mesh's agents in global
        coordinates (devices in order) and its substances reassembled."""
        out = state_dict(state)
        if self.dcfg is not None:
            out = global_state(self.dcfg, out, self.resolution)
        return out


def state_dict(state) -> dict:
    import jax
    import numpy as np

    pool = state.pool
    out = jax.device_get({
        "position": pool.position, "diameter": pool.diameter,
        "kind": pool.kind, "age": pool.age, "alive": pool.alive,
        "attrs": dict(pool.attrs), "rng": state.rng, "step": state.step,
        "grids": {k: g.concentration for k, g in state.grids.items()},
        "health": {f.name: getattr(state.health, f.name)
                   for f in dataclasses.fields(state.health)},
    })
    out["step"] = int(np.asarray(out["step"]).ravel()[0])
    out["health"] = {k: int(np.asarray(v).sum())
                     for k, v in out["health"].items()}
    return out


def global_state(dcfg, s: dict, resolution: dict) -> dict:
    """Stacked per-device arrays -> one global pool and global grids."""
    import numpy as np

    n_dev = s["position"].shape[0]
    pos = s["position"].copy()
    for dev in range(n_dev):
        for d, c in enumerate(dcfg.device_coords(dev)):
            pos[dev, :, d] += c * dcfg.extent
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    grids = {}
    for name, frames in s["grids"].items():
        per, res = frames.shape[1:], (resolution[name],) * 3
        g = np.zeros(res, frames.dtype)
        for dev in range(n_dev):
            coords = list(dcfg.device_coords(dev)) + [0] * (3 - dcfg.n_decomposed)
            lo = [coords[d] * per[d] for d in range(3)]
            hi = [min(lo[d] + per[d], res[d]) for d in range(3)]
            g[lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]] = frames[dev][
                :hi[0] - lo[0], :hi[1] - lo[1], :hi[2] - lo[2]]
        grids[name] = g
    return dict(s, position=flat(pos), diameter=flat(s["diameter"]),
                kind=flat(s["kind"]), age=flat(s["age"]),
                alive=flat(s["alive"]),
                attrs={k: flat(v) for k, v in s["attrs"].items()},
                rng=s["rng"][0], grids=grids)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             agents: int | None = None, fault=None,
             keep_states: bool = False, chunks: int | None = None) -> dict:
    """Set-up, window and check of one run → the result's fields.

    The check compares the window's first chunk and its last (one chunk when
    the window holds one) with the plain reference, and holds every health
    counter of the final state to 0.  ``fault`` (tests only) replaces each
    window chunk's output state with ``fault(before, after)``, to see the
    check fail on a broken step; ``chunks`` (tests only) ends the window
    after that many chunks instead of by time.  ``keep_states`` returns the
    compared chunks' host states as well (``(before, got, want)`` per
    chunk), for the control.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import population

    cfg = cell.cfg if agents is None else population.resized(cell.cfg, agents)
    chunk = cell.params["chunk_steps"]

    t0 = time.perf_counter()
    target = Target(cell, cfg, seed)
    count_live = jax.jit(lambda st: jnp.sum(st.pool.alive.astype(jnp.int32)))

    def run(st):
        with jax.profiler.TraceAnnotation("dispatch"):
            out = target.run(st, chunk)
        with jax.profiler.TraceAnnotation("block_until_ready"):
            return jax.block_until_ready(out)

    state = run(target.state)
    int(count_live(state))
    setup_s = time.perf_counter() - t0
    note(f"{cell.name}: {cfg['agents']} agents, grid {target.grid_dims}, "
         f"set-up {setup_s} s")

    if trace:
        # The first trace taken on a machine stalls the device for seconds
        # once it starts (2.9 s on v5e); a short trace here keeps that stall
        # out of the window.
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        int(count_live(state))
        jax.profiler.stop_trace()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    steps, updates = 0, 0
    first_step = int(np.asarray(state.step).ravel()[0])
    before = first = None
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            before = state
            state = run(before)
            t_end = time.perf_counter()
            if fault is not None:
                state = fault(before, state)
            if first is None:
                first = (before, state)
            with jax.profiler.TraceAnnotation("count_live"):
                updates += chunk * int(count_live(state))
            steps += chunk
            if (t_end - t_start >= seconds if chunks is None
                    else steps // chunk >= chunks):
                break
    window_s = t_end - t_start
    if trace:
        jax.profiler.stop_trace()

    # The TPU runtime keeps a program's temporaries in reserved memory,
    # apart from the buffers in use: a chip is as full as the two together.
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
               for st in stats)
    note(f"memory_stats: {stats[0]}")
    note(f"{cell.name}: {steps} steps from step {first_step} in {window_s} s")

    result = {"steps": steps, "window_s": window_s, "updates": updates,
              "setup_s": setup_s, "peak_bytes": peak,
              "first_step": first_step, "chunks": steps // chunk}
    if trace:
        tr = load_module(os.path.join(BENCH, "trace.py"), "bench_trace")
        hlo = target.module_text(before, chunk)
        with open(os.path.join(TRACE_DIR, "module.txt"), "w") as f:
            f.write(hlo)
        result["trace"] = tr.Trace.load(TRACE_DIR, op_freq=target.op_freq,
                                        hlo_text=hlo, first_step=first_step,
                                        steps=steps)

    pairs = [first] if first[1] is state else [first, (before, state)]
    pairs = [(target.host_state(b), target.host_state(a)) for b, a in pairs]
    del target, state, before, first
    gc.collect()
    numbers, states = {}, []
    for got_before, got in pairs:
        with jax.default_matmul_precision("highest"):
            want = cell.check.reference(cfg, got_before, chunk)
        for k, v in cell.check.compare(cfg, got, want).items():
            numbers[k] = max(v, numbers.get(k, v))
        states.append((got_before, got, want))
    health = pairs[-1][1]["health"]
    result["checks"] = {k: {"value": v, "limit": cell.check.LIMITS[k]}
                        for k, v in numbers.items()}
    result["checks"].update({k: {"value": v, "limit": 0}
                             for k, v in health.items()})
    result["compared_chunks"] = len(pairs)
    result["health"] = health
    result["cfg"] = cfg
    if keep_states:
        result["states"] = states
    return result


def per_layer(cell: Cell, trace, cfg: dict, peaks: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                          f"metric_{m['name'].replace('.', '_')}")
        value = mod.read(trace, cfg, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--agents", type=int, default=None,
                    help="rehearse at this many agents; prints no result")
    args = ap.parse_args(argv)

    cell = Cell.load(args.workload)
    import jax

    devices = jax.devices()
    dev = devices[0]
    on_chip = dev.platform == "tpu" and len(devices) >= cell.chips
    if not on_chip and args.agents is None:
        note(f"{args.workload} needs {cell.chips} TPU chip(s); JAX finds "
             f"{len(devices)} {dev.platform} device(s): no result")
        return 2
    peaks = read_json("peaks.json")
    if on_chip and dev.device_kind not in peaks:
        note(f"no peaks for device kind {dev.device_kind!r} in peaks.json")
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    r = run_cell(cell, args.seed, args.seconds, bool(args.trace), args.agents)
    correct = all(c["value"] <= c["limit"] for c in r["checks"].values())
    note(f"compared chunks: {r['compared_chunks']} (first and last of the "
         f"window)")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": r["peak_bytes"]}
    if args.trace:
        t = r["trace"]
        metrics = per_layer(cell, t, r["cfg"], peaks.get(dev.device_kind))
        device.update(busy_s=t.busy_s, window_s=t.window_s)
        breakdown = t.breakdown()
    else:
        values = {
            "agent_updates_per_s": r["updates"] / r["window_s"],
            "peak_hbm_gb": r["peak_bytes"] / 1e9,
            "setup_s": r["setup_s"],
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    for k, v in metrics.items():
        note(f"metric {k}: {v['value']} {v['unit']}")
    if not on_chip:
        note(f"rehearsal on {dev.platform} {'passed' if correct else 'FAILED'}"
             f"; not a chip run: no result")
    for k, c in r["checks"].items():
        note(f"check {k}: {c['value']} (limit {c['limit']})")
    if not on_chip:
        return 1
    line = {"correct": correct, "attempted": r["chunks"],
            "failed": 0 if correct else 1, "metrics": metrics,
            "device": device}
    if args.trace:
        line["breakdown"] = breakdown
    line["checks"] = r["checks"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
