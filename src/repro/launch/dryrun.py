import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = os.environ.get("REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any jax import: jax locks the device count on first init.
# REPRO_XLA_FLAGS lets tests use smaller placeholder device counts.  Every
# dry-run is a CPU simulation over placeholder devices, so it never claims an
# accelerator another process holds.

# Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
# cell with ShapeDtypeStruct stand-ins (no allocation), print memory/cost
# analysis, and derive the three-term roofline (compute / HBM / ICI-collective).
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
#     PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single  # 40 cells
#
# SU3 fig7 multi-controller dry-run: ONE launch, N identical controller
# processes, each running the full strong-scaling curve through the real
# (host, device) MeshSpec plan path over forced host-platform devices; the
# launcher fails on any divergence between controllers or from the d1
# single-host reference.  (jaxlib's CPU backend cannot run cross-process
# computations, so the controllers are replicas of the same SPMD program —
# the multi-controller *protocol* under simulation, byte-checked.)
#
#     PYTHONPATH=src python -m repro.launch.dryrun --su3-fig7 \
#         --L 4 --device-counts 1,2 --hosts 2 --controllers 2
#
# (Module docstring sacrificed to keep the XLA_FLAGS lines first, per the
# dry-run contract; `from __future__` must follow a docstring if present.)

import argparse
import dataclasses
import hashlib
import json
import pathlib
import subprocess
import sys
import tempfile
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import roofline
from repro.distributed import act_sharding, sharding
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import common, registry
from repro.optim import adamw
from repro.train.train_step import make_train_step

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


@dataclasses.dataclass
class CellPolicy:
    """Memory/precision policy for a cell (recorded in the report)."""

    param_dtype: str
    moment_dtype: str
    cache_dtype: str
    microbatches: int

    @staticmethod
    def for_cell(cfg: ModelConfig, shape: ShapeConfig) -> "CellPolicy":
        big = cfg.n_params() > 60e9
        if shape.kind == "train":
            mb = 1
            if shape.seq_len * shape.global_batch >= 2**20:
                mb = 16 if big else 4
            return CellPolicy(
                param_dtype="bfloat16" if big else "float32",
                moment_dtype="bfloat16" if big else "float32",
                cache_dtype="bfloat16",
                microbatches=mb,
            )
        return CellPolicy(
            param_dtype="bfloat16", moment_dtype="bfloat16",
            cache_dtype="bfloat16", microbatches=1,
        )


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Assignment formula: 6*N*D train (N_active for MoE), 2*N*D inference."""
    n = cfg.active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    return (6.0 if shape.kind == "train" else 2.0) * n * tokens


def _sharded_bytes(spec_tree, mesh, rules, dtype) -> int:
    """Exact per-device bytes of a ParamSpec tree under the resolved shardings."""
    total = 0
    leaves = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, common.ParamSpec))
    for s in leaves:
        pspec = sharding.resolve_spec(s.axes, s.shape, mesh, rules)
        local = 1
        for i, dim in enumerate(s.shape):
            ax = pspec[i] if i < len(pspec) else None
            div = 1
            if ax is not None:
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a in axes:
                    div *= mesh.shape[a]
            local *= dim // div
        total += local * jnp.dtype(dtype).itemsize
    return total


def _state_bytes(state_sds, mesh, rules, kv_seq_shard=False) -> int:
    """Per-device bytes of the decode/prefill state under state_shardings."""
    shardings = sharding.state_shardings(state_sds, mesh, rules, kv_seq_shard=kv_seq_shard)
    total = 0
    for sds, sh in zip(jax.tree.leaves(state_sds), jax.tree.leaves(shardings)):
        spec = sh.spec
        local = 1
        for i, dim in enumerate(sds.shape):
            ax = spec[i] if i < len(spec) else None
            div = 1
            if ax is not None:
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a in axes:
                    div *= mesh.shape[a]
            local *= dim // max(div, 1)
        total += local * jnp.dtype(sds.dtype).itemsize
    return total


def estimate_memory(
    cfg: ModelConfig, shape: ShapeConfig, mesh: jax.sharding.Mesh,
    rules, policy: "CellPolicy", api, *, kv_seq_shard: bool = False,
) -> dict[str, Any]:
    """TPU-side analytic memory model (per device).

    The XLA *CPU* backend has no native bf16 dot: FloatNormalization upcasts
    every bf16 matmul operand to f32 and hoists whole-stack converts, so
    ``memory_analysis()`` on the host backend over-reports bf16 programs by
    up to ~3x (verified on the qwen3 train cell: 22.5 GiB hoisted f32 copy
    of an 11.25 GiB bf16 residual stack). This analytic model is the
    TPU-faithful estimate; both are recorded.
    """
    spec_tree = api.spec(cfg)
    p_bytes = _sharded_bytes(spec_tree, mesh, rules, policy.param_dtype)
    out: dict[str, Any] = {"params_bytes": p_bytes}
    dp = 1
    for a in rules.data_axes:
        dp *= mesh.shape[a]
    if shape.kind == "train":
        m_bytes = _sharded_bytes(spec_tree, mesh, rules, policy.moment_dtype)
        g_bytes = _sharded_bytes(spec_tree, mesh, rules, "float32")
        tokens_local = shape.global_batch * shape.seq_len // max(policy.microbatches, 1) // dp
        # remat residual stacks: one (d_model) vector per layer per local token
        resid = cfg.n_layers * tokens_local * cfg.d_model * 2  # bf16
        # transient working set ~ one layer's widest intermediate x2
        widest = max(cfg.d_ff, cfg.d_model * 4, cfg.ssm_expand * cfg.d_model * 2)
        trans = 2 * tokens_local * widest * 4
        out.update(
            opt_bytes=2 * m_bytes, grad_bytes=g_bytes,
            residual_bytes=resid, transient_bytes=trans,
            total_bytes=p_bytes + 2 * m_bytes + g_bytes + resid + trans,
        )
    else:
        state_sds = api.state_spec(cfg, shape.global_batch, shape.seq_len,
                                   jnp.dtype(policy.cache_dtype))
        s_bytes = _state_bytes(state_sds, mesh, rules, kv_seq_shard=kv_seq_shard)
        tokens_local = max(shape.global_batch * (shape.seq_len if shape.kind == "prefill" else 1) // dp, 1)
        widest = max(cfg.d_ff, cfg.d_model * 4, cfg.ssm_expand * cfg.d_model * 2)
        trans = 2 * tokens_local * widest * 2
        out.update(
            state_bytes=s_bytes, transient_bytes=trans,
            total_bytes=p_bytes + s_bytes + trans,
        )
    out["fits_v5e_16g"] = out["total_bytes"] <= roofline.TPU_V5E.hbm_bytes
    return out


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: jax.sharding.Mesh,
    *,
    policy: CellPolicy | None = None,
    fsdp: bool = True,
    kv_seq_shard: bool = False,
    grad_acc_dtype: str = "float32",
    microbatches: int | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
):
    """Build + lower one cell. Returns (lowered, meta)."""
    policy = policy or CellPolicy.for_cell(cfg, shape)
    if microbatches is not None:
        policy = dataclasses.replace(policy, microbatches=microbatches)
    rules = sharding.default_rules(mesh, fsdp=fsdp)
    api = registry.get(cfg)
    spec_tree = api.spec(cfg)
    p_dt = jnp.dtype(policy.param_dtype)
    params_sds = common.shape_tree(spec_tree, dtype=p_dt)
    p_sh = sharding.param_shardings(spec_tree, mesh, rules)
    batch_sds = registry.input_specs(cfg, shape)
    b_sh = sharding.batch_shardings(batch_sds, mesh, rules)

    with jax.set_mesh(mesh), act_sharding.use_rules(mesh, rules):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig(moment_dtype=policy.moment_dtype)
            m_dt = jnp.dtype(policy.moment_dtype)
            opt_sds = {
                "m": common.shape_tree(spec_tree, dtype=m_dt),
                "v": common.shape_tree(spec_tree, dtype=m_dt),
                "count": jax.ShapeDtypeStruct((), jnp.int32),
            }
            opt_sh = sharding.opt_state_shardings(p_sh, mesh)
            step = make_train_step(
                cfg, opt_cfg, microbatches=policy.microbatches,
                grad_acc_dtype=grad_acc_dtype, param_shardings=p_sh,
                q_chunk=q_chunk, kv_chunk=kv_chunk,
            )
            fn = jax.jit(
                step,
                in_shardings=(p_sh, opt_sh, b_sh),
                out_shardings=(p_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(params_sds, opt_sds, batch_sds)
        else:
            c_dt = jnp.dtype(policy.cache_dtype)
            state_sds = api.state_spec(cfg, shape.global_batch, shape.seq_len, c_dt)
            s_sh = sharding.state_shardings(state_sds, mesh, rules, kv_seq_shard=kv_seq_shard)
            if shape.kind == "prefill":

                def prefill_fn(params, batch, state):
                    return api.prefill(params, batch, state, cfg,
                                       q_chunk=q_chunk, kv_chunk=kv_chunk)

                fn = jax.jit(
                    prefill_fn,
                    in_shardings=(p_sh, b_sh, s_sh),
                    out_shardings=(None, s_sh),
                    donate_argnums=(2,),
                )
                lowered = fn.lower(params_sds, batch_sds, state_sds)
            else:  # decode

                def decode_fn(params, batch, state, cur_len):
                    return api.decode_step(params, batch, state, cur_len, cfg)

                cur_sds = jax.ShapeDtypeStruct((), jnp.int32)
                fn = jax.jit(
                    decode_fn,
                    in_shardings=(p_sh, b_sh, s_sh, None),
                    out_shardings=(None, s_sh),
                    donate_argnums=(2,),
                )
                lowered = fn.lower(params_sds, batch_sds, state_sds, cur_sds)
    meta = {"policy": dataclasses.asdict(policy), "fsdp": fsdp,
            "kv_seq_shard": kv_seq_shard, "grad_acc_dtype": grad_acc_dtype,
            "q_chunk": q_chunk, "kv_chunk": kv_chunk}
    return lowered, meta


def run_cell(
    arch: str,
    shape_name: str,
    mesh: jax.sharding.Mesh,
    mesh_label: str,
    *,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
    verbose: bool = True,
    save_hlo: bool = False,
    overrides: dict[str, Any] | None = None,
    tag: str = "",
) -> dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    cell = f"{arch}/{shape_name}/{mesh_label}{('#' + tag) if tag else ''}"
    if not ok:
        if verbose:
            print(f"[skip] {cell}: {reason}")
        return {"cell": cell, "status": "skipped", "reason": reason}

    t0 = time.time()
    lowered, meta = lower_cell(cfg, shape, mesh, **(overrides or {}))
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_report = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    mem_report["total_bytes_per_device"] = (
        mem_report["argument_bytes"]
        + mem_report["output_bytes"]
        + mem_report["temp_bytes"]
        - mem_report["alias_bytes"]
    )
    mem_report["fits_v5e_16g"] = mem_report["total_bytes_per_device"] <= hw.hbm_bytes
    # TPU-faithful analytic model (the CPU backend f32-upcasts bf16 dots,
    # inflating temp bytes; see estimate_memory docstring).
    cfg_policy = CellPolicy(**meta["policy"]) if isinstance(meta.get("policy"), dict) else None
    rules = sharding.default_rules(mesh, fsdp=meta.get("fsdp", True))
    analytic = estimate_memory(
        cfg, shape, mesh, rules, cfg_policy or CellPolicy.for_cell(cfg, shape),
        registry.get(cfg), kv_seq_shard=bool(meta.get("kv_seq_shard", False)),
    )

    hlo_text = compiled.as_text()
    report = roofline.analyze_compiled(
        cell, compiled, n_chips=mesh.devices.size, hw=hw,
        model_flops=model_flops(cfg, shape), hlo_text=hlo_text,
    )

    out = {
        "cell": cell,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_label,
        "n_devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_report,
        "memory_analytic": analytic,
        "roofline": report.as_dict(),
        **meta,
    }
    if verbose:
        gib = mem_report["total_bytes_per_device"] / 2**30
        agib = analytic["total_bytes_per_device" if "total_bytes_per_device" in analytic else "total_bytes"] / 2**30
        print(f"[ok] {cell}: compile {t_compile:.1f}s | xla {gib:.2f} GiB/dev, "
              f"analytic {agib:.2f} GiB/dev (fits v5e: {analytic['fits_v5e_16g']})")
        print("     " + report.summary())
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = RESULTS_DIR / f"{arch}__{shape_name}__{mesh_label}{suffix}.json"
    fname.write_text(json.dumps(out, indent=2, default=str))
    if save_hlo:
        (RESULTS_DIR / f"{arch}__{shape_name}__{mesh_label}{suffix}.hlo.txt").write_text(hlo_text)
    return out


# ---------------------------------------------------------------------------
# SU3 fig7: strong scaling as ONE multi-controller dry-run launch
# ---------------------------------------------------------------------------


def _su3_result_digest(plan, seed: int) -> str:
    """sha256 of the canonical C lattice from a seeded random (A, B) pair.

    The SU3 multiply is site-local, so the live-site bytes are identical
    across every mesh/sharding of the same program — any difference between
    controllers or device counts is a real divergence (sharding permutation,
    init bug, nondeterminism), which is exactly what the launcher gates on.

    The RNG draw covers exactly the L**4 live sites (NOT ``padded_sites``,
    which varies with the device count and would shift the stream, making
    legitimately-identical results digest differently); padding is
    deterministic zeros and ``plan.unpack`` slices back to the live sites
    before hashing.
    """
    import numpy as np

    n = plan.cfg.shape.n_sites
    rng = np.random.default_rng(seed)
    shape = (n, 4, 3, 3)
    a = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype("complex64")
    b = (rng.standard_normal((4, 3, 3)) + 1j * rng.standard_normal((4, 3, 3))).astype("complex64")
    a = np.concatenate(
        [a, np.zeros((plan.padded_sites - n, 4, 3, 3), "complex64")], axis=0
    )
    c_phys = plan.step(plan.codec.pack(jnp.asarray(a)), plan.codec.pack_b(jnp.asarray(b)))
    c = np.asarray(jax.device_get(plan.unpack(c_phys)))  # live sites only
    return hashlib.sha256(c.tobytes()).hexdigest()


def su3_fig7_rows(
    L: int,
    device_counts: tuple[int, ...],
    hosts: int,
    seed: int = 0,
    iterations: int = 3,
) -> tuple[list[dict], dict[str, str]]:
    """The fig7 strong-scaling curve over (host, device) MeshSpec plans.

    Runs in ONE process whose forced device pool covers ``max(device_counts)``;
    every point slices its mesh from that pool through
    :class:`repro.launch.mesh.MeshSpec` — the real ``build_plan`` multi-host
    path, not a per-point child process.

    Returns:
        ``(rows, digests)`` — benchmark rows named ``fig7_{placement}_d{n}``
        (schema-compatible with the historical fig7 rows, plus ``hosts`` and
        halo fields) and ``{point_name: result_sha256}`` for the launcher's
        divergence gate.
    """
    from repro.core.su3.engine import EngineConfig as SU3EngineConfig, SU3Engine
    from repro.launch.mesh import MeshSpec

    rows: list[dict] = []
    digests: dict[str, str] = {}
    for n in device_counts:
        h = min(hosts, n)
        spec = MeshSpec(hosts=h, devices_per_host=n // h)
        for placement in ("sharded", "host_scatter"):
            cfg = SU3EngineConfig(
                L=L, variant="versionX", placement=placement,
                iterations=iterations, warmups=1, tile=128,
            )
            eng = SU3Engine(cfg, spec)
            row = eng.run().row()
            row["name"] = f"fig7_{placement}_d{n}"
            row["hosts"] = h
            row.update(eng.plan.halo().as_dict() if L**4 % max(h, 1) == 0 else {})
            rows.append(row)
            if placement == "sharded":
                digests[f"d{n}"] = _su3_result_digest(eng.plan, seed)
    return rows, digests


def _su3_fig7_worker(args: argparse.Namespace) -> None:
    """One controller: compute the curve + digests, write them to a JSON."""
    counts = tuple(int(x) for x in args.device_counts.split(","))
    rows, digests = su3_fig7_rows(
        args.L, counts, args.hosts, seed=args.seed, iterations=args.iterations
    )
    payload = {
        "rank": args.rank,
        "n_devices_visible": len(jax.devices()),
        "rows": rows,
        "digests": digests,
    }
    pathlib.Path(args.out).write_text(json.dumps(payload, default=str))


def su3_fig7_launch(
    L: int,
    device_counts: tuple[int, ...],
    hosts: int,
    controllers: int,
    seed: int = 0,
    iterations: int = 3,
    timeout: int = 600,
) -> list[dict]:
    """Launch ``controllers`` identical fig7 workers; gate on divergence.

    Every worker runs the full curve (the multi-controller SPMD discipline:
    same program, same data, every rank).  The launcher then requires

      * within each controller: every device count's result digest equals
        that controller's d1 (single-host) digest;
      * across controllers: all digest tables identical.

    Raises SystemExit(1) on divergence.  Returns controller 0's rows, each
    stamped with ``controllers``.
    """
    counts = ",".join(str(c) for c in device_counts)
    max_dev = max(device_counts)
    outs = []
    procs = []
    tmpdir = tempfile.mkdtemp(prefix="su3_fig7_")
    env = dict(os.environ)
    env["REPRO_XLA_FLAGS"] = f"--xla_force_host_platform_device_count={max_dev}"
    env["JAX_PLATFORMS"] = "cpu"  # simulated controllers never take the chip
    env.setdefault("PYTHONPATH", str(pathlib.Path(__file__).resolve().parents[2]))
    for rank in range(controllers):
        out = pathlib.Path(tmpdir) / f"controller_{rank}.json"
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--su3-fig7-worker", "--rank", str(rank), "--out", str(out),
             "--L", str(L), "--device-counts", counts, "--hosts", str(hosts),
             "--seed", str(seed), "--iterations", str(iterations)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    payloads = []
    for rank, proc in enumerate(procs):
        try:
            _, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise SystemExit(f"su3-fig7 controller {rank} timed out")
        if proc.returncode != 0:
            raise SystemExit(
                f"su3-fig7 controller {rank} failed:\n{err[-2000:]}"
            )
        payloads.append(json.loads(outs[rank].read_text()))

    reference = payloads[0]["digests"]
    single_host = reference.get(f"d{min(device_counts)}")
    failures = []
    for p in payloads:
        for point, digest in p["digests"].items():
            if digest != single_host:
                failures.append(
                    f"controller {p['rank']} {point}: {digest[:12]} != "
                    f"single-host {str(single_host)[:12]}"
                )
        if p["digests"] != reference:
            failures.append(f"controller {p['rank']} digest table diverges from rank 0")
    if failures:
        for f in failures:
            print(f"[DIVERGENCE] {f}", file=sys.stderr)
        raise SystemExit(1)
    rows = payloads[0]["rows"]
    for row in rows:
        row["controllers"] = controllers
    return rows


def _mesh_for(label: str) -> jax.sharding.Mesh:
    n = len(jax.devices())
    if label == "multi":
        if n >= 512:
            return make_production_mesh(multi_pod=True)
        # reduced-device fallback (tests): keep 3-axis structure
        return make_mesh((2, 2, n // 4), ("pod", "data", "model"))
    if n >= 256:
        return make_production_mesh(multi_pod=False)
    return make_mesh((max(n // 8, 1), min(n, 8)), ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true", help="run every applicable cell")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--kv-seq-shard", action="store_true",
                    help="shard KV cache sequence dim over model axis when "
                         "kv_heads cannot (flash-decoding style)")
    ap.add_argument("--grad-acc-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--tag", default="", help="suffix for the result JSON")
    # SU3 fig7 multi-controller dry-run
    ap.add_argument("--su3-fig7", action="store_true",
                    help="launch the SU3 strong-scaling curve as one "
                         "multi-controller dry-run (divergence-gated)")
    ap.add_argument("--su3-fig7-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one controller rank
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--L", type=int, default=8)
    ap.add_argument("--device-counts", default="1,2,4")
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--controllers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iterations", type=int, default=3)
    args = ap.parse_args()

    if args.su3_fig7_worker:
        _su3_fig7_worker(args)
        return
    if args.su3_fig7:
        counts = tuple(int(x) for x in args.device_counts.split(","))
        rows = su3_fig7_launch(
            args.L, counts, args.hosts, args.controllers,
            seed=args.seed, iterations=args.iterations,
        )
        print(json.dumps(rows, default=str))
        return

    mesh_labels = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a in ALL_ARCHS for s in SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    overrides: dict[str, Any] = {}
    if args.no_fsdp:
        overrides["fsdp"] = False
    if args.kv_seq_shard:
        overrides["kv_seq_shard"] = True
    if args.grad_acc_dtype != "float32":
        overrides["grad_acc_dtype"] = args.grad_acc_dtype
    if args.microbatches is not None:
        overrides["microbatches"] = args.microbatches
    failures = 0
    for label in mesh_labels:
        mesh = _mesh_for(label)
        print(f"== mesh {label}: {dict(zip(mesh.axis_names, mesh.devices.shape))} ==")
        for arch, shape_name in cells:
            try:
                run_cell(arch, shape_name, mesh, label, save_hlo=args.save_hlo,
                         overrides=overrides, tag=args.tag)
            except Exception as e:  # a failing cell is a bug in the system
                failures += 1
                print(f"[FAIL] {arch}/{shape_name}/{label}: {type(e).__name__}: {e}")
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
