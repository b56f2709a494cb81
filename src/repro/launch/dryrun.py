import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
    lowered  = jax.jit(step).lower(**input ShapeDtypeStructs w/ shardings)
    compiled = lowered.compile()
    print(compiled.memory_analysis())     # proves it fits
    print(compiled.cost_analysis())       # FLOPs/bytes for §Roofline
plus a collective-bytes scan of the compiled HLO (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes), which
cost_analysis does not report.

Usage:
    python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
    python -m repro.launch.dryrun --arch teraagent --mesh multi   (ABM engine)

NOTE the two lines above this docstring: XLA must see 512 host devices
before any jax import, and only in this entry point — tests/benches keep the
real single-device view.
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro import training
from repro.configs import ARCHS, SHAPES, get_config, input_specs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.optim import adamw

# ---------------------------------------------------------------------------
# v5e hardware constants (roofline denominators)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # B/s per chip
ICI_BW = 50e9             # B/s per link

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes of every collective op in the (SPMD, per-device)
    HLO.  Returns {op_kind: bytes, ..., "total": bytes}."""
    dtype_bytes = {
        "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
        "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
        "s8": 1, "u8": 1, "pred": 1,
    }

    shape_of: Dict[str, str] = {}
    def parse_shape(s: str) -> float:
        m = re.match(r"\(?(\w+)\[([\d,]*)\]", s)
        if not m:
            return 0.0
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        return n * dtype_bytes.get(dt, 4)

    # map instruction name -> shape string (covers tuple-free results)
    for m in re.finditer(r"(%?[\w.\-]+) = ((?:\([^)]*\))|(?:\w+\[[^\]]*\][^ ]*)) ", hlo_text):
        shape_of[m.group(1).lstrip("%")] = m.group(2)

    out = {k: 0.0 for k in _COLLECTIVES}
    pattern = re.compile(
        r"= (?:\([^)]*\)|\w+\[[^\]]*\][^ ]*) (" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(([^)]*)\)"
    )
    for m in pattern.finditer(hlo_text):
        kind = m.group(1)
        args = m.group(2)
        total = 0.0
        for arg in args.split(","):
            arg = arg.strip()
            am = re.match(r"(\w+\[[^\]]*\][^ ]*)? ?%?([\w.\-]+)", arg)
            if not am:
                continue
            if am.group(1):
                total += parse_shape(am.group(1))
            else:
                ref = am.group(2)
                if ref in shape_of:
                    sstr = shape_of[ref]
                    if sstr.startswith("("):
                        for sub in re.findall(r"\w+\[[\d,]*\]", sstr):
                            total += parse_shape(sub)
                    else:
                        total += parse_shape(sstr)
        # X-start/X-done pairs would double count: only count -start or bare
        out[kind] += total
    # halve nothing: finditer sees each textual op once per occurrence of
    # "-start" and "-done"; exclude "-done" by requiring operands non-ref?
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


_HBM_OPS = (
    "dot", "fusion", "copy", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "reduce", "transpose", "pad", "concatenate",
    "reduce-window", "sort", "iota2",  # iota2 never matches; placeholder
)


def fused_bytes_from_hlo(hlo_text: str) -> float:
    """Fusion-granularity HBM-traffic estimate (per device).

    XLA:CPU's cost_analysis counts operand/result bytes of *every* op,
    including elementwise chains that XLA:TPU fuses into single VMEM-
    resident kernels — inflating the memory term ~10–40×.  This estimate
    sums result + operand bytes only for ops that materialize HBM buffers
    on TPU (dots, fusion roots, copies, gathers/scatters, reduces,
    layout ops), which brackets real HBM traffic far more tightly.  Both
    numbers are reported; the roofline dominant-term uses this one."""
    dtype_bytes = {
        "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
        "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    }

    def shape_bytes(s: str) -> float:
        total = 0.0
        for m in re.finditer(r"(\w+)\[([\d,]*)\]", s):
            n = 1
            for d in m.group(2).split(","):
                if d:
                    n *= int(d)
            total += n * dtype_bytes.get(m.group(1), 4)
        return total

    shape_of: Dict[str, float] = {}
    for m in re.finditer(
        r"(%?[\w.\-]+) = ((?:\([^)]*\))|(?:\w+\[[^\]]*\][^ ]*)) ", hlo_text
    ):
        shape_of[m.group(1).lstrip("%")] = shape_bytes(m.group(2))

    total = 0.0
    op_alt = "|".join(_HBM_OPS)
    pattern = re.compile(
        r"= ((?:\([^)]*\))|(?:\w+\[[^\]]*\][^ ]*)) (" + op_alt + r")\(([^)]*)\)"
    )
    # "write once + read once" model: every materialized buffer costs 2×
    # its result bytes; producer-consumer operand bytes are thereby counted
    # exactly once without chasing references (no double counting).
    for m in pattern.finditer(hlo_text):
        total += 2.0 * shape_bytes(m.group(1))
    return total


def _strip_done_ops(hlo_text: str) -> str:
    """Remove async -done lines so start/done pairs count once."""
    return "\n".join(
        ln for ln in hlo_text.splitlines()
        if not re.search(r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)-done", ln)
    )


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, sequence_parallel: bool = True,
               attention_impl: Optional[str] = None, cfg=None):
    """Build + lower one (arch × shape) on the mesh.  Returns jax Lowered."""
    if cfg is None:
        cfg = get_config(arch)
    if attention_impl:
        cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
    if os.environ.get("DRYRUN_REMAT_POLICY"):
        cfg = dataclasses.replace(
            cfg, remat_policy=os.environ["DRYRUN_REMAT_POLICY"]
        )
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        raise SkipCell(reason)

    model = build_model(cfg)
    if shape.kind == "train":
        model.residual_sharding = jax.sharding.NamedSharding(
            mesh, sh.activation_spec(mesh, sequence_parallel)
        )
    # §Perf iteration (MoE): pin the dispatch buffer's expert dim to the
    # tensor axis so expert gradients stay sharded through the backward.
    if cfg.is_moe and os.environ.get("DRYRUN_NO_EXPERT_SHARDING") != "1":
        model.expert_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("model", None, None)
        )
    # §Perf iteration (phi4/whisper/paligemma): when the q-head count does
    # not divide the tensor axis, attention-head compute would replicate —
    # shard the query-block (context) dim over "model" instead.
    model_size = mesh.shape.get("model", 1)
    if (
        shape.kind in ("train", "prefill")
        and cfg.n_heads % model_size != 0
        and os.environ.get("DRYRUN_NO_CONTEXT_PARALLEL") != "1"
    ):
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        # the GQA-folded q-block dim (group · T/block_q) must divide the
        # tensor axis; shrink block_q until it does
        group = cfg.n_heads // cfg.n_kv_heads
        bq = cfg.attention_block_q
        while bq > 128 and (group * (shape.seq_len // bq)) % model_size != 0:
            bq //= 2
        if (group * (shape.seq_len // bq)) % model_size == 0:
            if bq != cfg.attention_block_q:
                cfg = dataclasses.replace(cfg, attention_block_q=bq)
                model = build_model(cfg)
                if shape.kind == "train":
                    model.residual_sharding = jax.sharding.NamedSharding(
                        mesh, sh.activation_spec(mesh, sequence_parallel)
                    )
                if cfg.is_moe and os.environ.get("DRYRUN_NO_EXPERT_SHARDING") != "1":
                    model.expert_sharding = jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec("model", None, None)
                    )
            model.context_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(dp, None, "model", None, None)
            )

    batch_sds = input_specs(cfg, shape)
    batch_sharded = training.attach_shardings(
        batch_sds, sh.batch_specs(mesh, batch_sds)
    )

    if shape.kind == "train":
        state, axes = training.eval_train_state(model)
        st_sh = training.state_shardings(mesh, state, axes)
        state_sds = training.attach_shardings(state, st_sh)
        opt_cfg = adamw.AdamWConfig()
        step_fn = training.make_train_step(model, opt_cfg)
        return jax.jit(step_fn, donate_argnums=(0,)).lower(state_sds, batch_sharded)

    # serve paths need only params
    params, axes = training.eval_params(model)
    p_sh = sh.param_shardings(mesh, params, axes)
    params_sds = training.attach_shardings(params, p_sh)

    if shape.kind == "prefill":
        step_fn = training.make_prefill_step(model)
        return jax.jit(step_fn).lower(params_sds, batch_sharded)

    # decode
    cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len)
    )
    cache_sds = training.attach_shardings(
        cache, sh.cache_shardings(mesh, cache, cfg.n_kv_heads)
    )
    tok_sds = jax.ShapeDtypeStruct(
        (shape.global_batch, 1), jnp.int32,
        sharding=sh.batch_sharding(mesh) if shape.global_batch % _dp_size(mesh) == 0
        else jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    step_fn = training.make_decode_step(model)
    return jax.jit(step_fn, donate_argnums=(1,)).lower(
        params_sds, cache_sds, tok_sds, pos_sds
    )


def _dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.shape]))


class SkipCell(Exception):
    pass


def _cell_costs(lowered) -> Dict[str, float]:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    hlo = _strip_done_ops(compiled.as_text())
    coll = collective_bytes_from_hlo(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "bytes_fused": fused_bytes_from_hlo(hlo),
        "coll": coll,
    }


def extrapolated_costs(arch: str, shape_name: str, mesh,
                       sequence_parallel: bool = True) -> Dict[str, float]:
    """Exact per-layer cost extrapolation via two shallow unrolled variants.

    XLA's HloCostAnalysis counts a while-loop body ONCE (not × trip count),
    so the scanned-layer program under-reports flops/bytes by ~n_layers×.
    We lower two fully-unrolled variants with L = g and L = 2g layers (g =
    block-pattern length; inner scans unrolled too) — the difference is the
    exact cost of g layers, and  total = A + (L_full − g)/g · (B − A)
    reconstructs the full-depth cost with the outside-the-layers part
    (embedding, logits+loss chunks, optimizer) counted exactly once."""
    cfg0 = get_config(arch)
    g = len(cfg0.block_pattern)
    l_full = cfg0.n_layers
    if cfg0.is_encoder_decoder:
        enc_a = max(1, round(cfg0.n_encoder_layers * g / l_full))
    else:
        enc_a = 0

    def costs_for(nl, ne):
        cfg = dataclasses.replace(
            cfg0, n_layers=nl, n_encoder_layers=ne,
            scan_layers=False, unroll_inner_scans=True,
        )
        lowered = lower_cell(arch, shape_name, mesh,
                             sequence_parallel=sequence_parallel, cfg=cfg)
        return _cell_costs(lowered)

    a = costs_for(g, enc_a)
    b = costs_for(2 * g, 2 * enc_a)
    factor = (l_full - g) / g
    out = {
        "flops": a["flops"] + factor * (b["flops"] - a["flops"]),
        "bytes": a["bytes"] + factor * (b["bytes"] - a["bytes"]),
        "bytes_fused": a["bytes_fused"] + factor * (b["bytes_fused"] - a["bytes_fused"]),
        "coll": {
            k: a["coll"][k] + factor * (b["coll"][k] - a["coll"][k])
            for k in a["coll"]
        },
        "shallow_a": a,
        "shallow_b": b,
    }
    return out


def lower_teraagent(mesh):
    """Dry-run cell for the paper's own workload: the distributed ABM step."""
    from repro.core import EngineConfig, ForceParams, brownian_motion
    from repro.core.distributed import (
        DistState, DomainConfig, GhostFrame, HaloCodecState,
        make_distributed_step,
    )
    from repro.core.agents import AgentPool

    axes = tuple(a for a in ("data", "model", "pod") if a in mesh.shape)
    sizes = tuple(mesh.shape[a] for a in axes)
    n_dev = int(np.prod(sizes))
    capacity = 1 << 20          # 1M agents per device → 0.25–0.5B agents total
    halo_cap = 1 << 15
    mig_cap = 1 << 13
    extent, halo = 64.0, 2.0
    dcfg = DomainConfig(
        mesh_axes=axes, axis_sizes=sizes, extent=extent, halo_width=halo,
        halo_capacity=halo_cap, migrate_capacity=mig_cap,
        depth=extent if len(axes) < 3 else 0.0, halo_codec="int16",
    )
    spec = dcfg.grid_spec(box_size=2.0, max_per_cell=32)
    force_tile = int(os.environ.get("DRYRUN_ABM_FORCE_TILE", "0")) or None
    ecfg = EngineConfig(
        spec=spec, behaviors=(brownian_motion(0.05),),
        force_params=ForceParams(), dt=0.05, min_bound=0.0, max_bound=extent,
        sort_frequency=16, force_tile=force_tile,
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    c = capacity
    pool = AgentPool(
        position=sds((n_dev, c, 3), jnp.float32),
        diameter=sds((n_dev, c), jnp.float32),
        kind=sds((n_dev, c), jnp.int32),
        age=sds((n_dev, c), jnp.float32),
        alive=sds((n_dev, c), jnp.bool_),
        static=sds((n_dev, c), jnp.bool_),
        attrs={},
        overflow=sds((n_dev,), jnp.int32),
    )
    codec = HaloCodecState(
        send_ref=sds((n_dev, len(axes), 2, halo_cap, 3), jnp.float32),
        recv_ref=sds((n_dev, len(axes), 2, halo_cap, 3), jnp.float32),
        prev_ids=sds((n_dev, len(axes), 2, halo_cap), jnp.int32),
        scale=sds((n_dev, 3), jnp.float32),
    )
    from repro.core.schedule import HealthReport

    state = DistState(
        pool=pool, grids={}, codec=codec,
        rng=sds((n_dev, 2), jnp.uint32),
        step=sds((n_dev,), jnp.int32),
        migrate_overflow=sds((n_dev,), jnp.int32),
        halo_overflow=sds((n_dev,), jnp.int32),
        halo_payload_bytes=sds((n_dev,), jnp.int32),
        halo_baseline_bytes=sds((n_dev,), jnp.int32),
        health=HealthReport(
            pool_overflow=sds((n_dev,), jnp.int32),
            migrate_overflow=sds((n_dev,), jnp.int32),
            halo_overflow=sds((n_dev,), jnp.int32),
            cell_overflow_steps=sds((n_dev,), jnp.int32),
            nonfinite_agents=sds((n_dev,), jnp.int32),
            nonfinite_steps=sds((n_dev,), jnp.int32),
        ),
        ghost=GhostFrame(
            position=sds((n_dev, 2 * len(axes) * halo_cap, 3), jnp.float32),
            radius=sds((n_dev, 2 * len(axes) * halo_cap), jnp.float32),
            kind=sds((n_dev, 2 * len(axes) * halo_cap), jnp.int32),
            alive=sds((n_dev, 2 * len(axes) * halo_cap), jnp.bool_),
        ),
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    leading = NamedSharding(mesh, P(axes))
    state_sharded = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=leading), state
    )
    step = make_distributed_step(mesh, dcfg, ecfg)
    return step.lower(state_sharded)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Optional[str],
             sequence_parallel: bool = True, verbose: bool = True) -> Dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    record: Dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": n_chips,
    }
    try:
        if arch == "teraagent":
            lowered = lower_teraagent(mesh)
            record["kind"] = "abm_step"
        else:
            lowered = lower_cell(arch, shape_name, mesh,
                                 sequence_parallel=sequence_parallel)
            record["kind"] = SHAPES[shape_name].kind
    except SkipCell as e:
        record["status"] = "skipped"
        record["reason"] = str(e)
        if verbose:
            print(f"[SKIP] {arch} × {shape_name} × {mesh_kind}: {e}")
        _write(out_dir, record)
        return record

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()

    # roofline terms from the exact shallow-differencing extrapolation
    # (the scanned full program under-counts while-loop bodies; see
    # extrapolated_costs) — the full compile above remains the memory /
    # compile-success proof.
    if arch == "teraagent":
        costs = _cell_costs(lowered)   # no layer scan: exact as-is
    else:
        costs = extrapolated_costs(arch, shape_name, mesh,
                                    sequence_parallel=sequence_parallel)
    flops = costs["flops"]
    bytes_acc = costs["bytes"]
    bytes_fused = costs["bytes_fused"]
    coll = costs["coll"]
    record.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=flops,
        bytes_accessed_per_device=bytes_acc,
        collective_bytes_per_device=coll,
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_estimate_bytes=(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        ),
        roofline=dict(
            compute_s=flops / PEAK_FLOPS,
            memory_s=bytes_acc / HBM_BW,
            memory_s_fused_est=bytes_fused / HBM_BW,
            collective_s=coll["total"] / ICI_BW,
        ),
    )
    terms = record["roofline"]
    record["roofline"]["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
    if arch != "teraagent":
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        tokens = shape.global_batch * (1 if record["kind"] == "decode" else shape.seq_len)
        n_active = cfg.params_active()
        model_flops_global = (6 if record["kind"] == "train" else 2) * n_active * tokens
        record["model_flops_per_device"] = model_flops_global / n_chips
        record["useful_flops_fraction"] = (
            record["model_flops_per_device"] / flops if flops else 0.0
        )
    if verbose:
        r = record["roofline"]
        print(
            f"[OK] {arch} × {shape_name} × {mesh_kind}: "
            f"compile {record['compile_s']}s, "
            f"compute {r['compute_s']*1e3:.2f}ms, mem {r['memory_s']*1e3:.2f}ms, "
            f"coll {r['collective_s']*1e3:.2f}ms → {r['dominant']}"
        )
        print(f"     memory: {record['memory']}")
    _write(out_dir, record)
    return record


def _write(out_dir, record):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    name = f"{record['mesh']}__{record['arch']}__{record.get('shape','-')}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id or 'teraagent'")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="every (arch × shape)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-sp", action="store_true", help="disable sequence parallelism")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in SHAPES:
                cells.append((arch, shape))
        cells.append(("teraagent", "train_4k"))
    else:
        assert args.arch, "--arch required without --all"
        shapes = [args.shape] if args.shape else list(SHAPES)
        if args.arch == "teraagent":
            shapes = ["train_4k"]
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for mesh_kind in meshes:
        for arch, shape in cells:
            name = f"{mesh_kind}__{arch}__{shape}.json"
            if args.skip_existing and os.path.exists(os.path.join(args.out, name)):
                print(f"[cached] {name}")
                continue
            try:
                run_cell(arch, shape, mesh_kind, args.out,
                         sequence_parallel=not args.no_sp)
            except Exception as e:
                traceback.print_exc()
                failures.append((mesh_kind, arch, shape, repr(e)))
                _write(args.out, {
                    "arch": arch, "shape": shape, "mesh": mesh_kind,
                    "status": "failed", "error": repr(e),
                })
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nAll dry-run cells passed.")


if __name__ == "__main__":
    main()
