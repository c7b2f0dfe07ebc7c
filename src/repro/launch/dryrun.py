"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes, and extract roofline terms from the compiled artifacts.

MUST be run as its own process: the first two lines force 512 host
platform devices BEFORE jax initializes (smoke tests and benches must see
1 device, so this is NOT set globally).

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, shape_applicable  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import Cell, build_cell  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.perf import roofline  # noqa: E402
from repro.train.trainer import TrainConfig  # noqa: E402


# Per-cell configuration overrides discovered during the §Perf iteration —
# see EXPERIMENTS.md for the hypothesis log behind each entry.
OVERRIDES = {
    # 400B params: bf16 moments + Kahan compensation instead of fp32
    # master state — the technique is what makes this fit 16 GiB chips.
    ("llama4-maverick-400b-a17b", "train_4k"): dict(
        opt=AdamWConfig(kahan=True, moment_dtype="bfloat16")),
}

# Per-cell sharding-rule overrides (§Perf I3c: xlstm loses seq sharding at
# every chunk reshape; batch-only activation sharding avoids the gathers).
RULE_OVERRIDES = {
    ("xlstm-1.3b", "train_4k"): "train_nosp",
}


def _map_specs(mesh, rules, spec_entry, shapes_entry):
    """Map a Cell arg/out spec entry to a NamedSharding tree."""
    if spec_entry is None:
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), shapes_entry)
    if spec_entry == "batch":
        return shd.batch_shardings(mesh, rules, shapes_entry)
    if spec_entry == "tokens1d":
        return shd.named_sharding(mesh, rules, P("batch"),
                                  tuple(shapes_entry.shape))
    return shd.tree_shardings(mesh, rules, spec_entry, shapes_entry)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             tc: TrainConfig = None, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    rules = shd.TRAIN_RULES if shape.kind == "train" else shd.SERVE_RULES
    if RULE_OVERRIDES.get((arch, shape_name)) == "train_nosp":
        rules = shd.TRAIN_NOSP_RULES

    if tc is None:
        over = OVERRIDES.get((arch, shape_name), {})
        tc = TrainConfig(**over) if over else TrainConfig()
    cell = build_cell(cfg, shape, tc=tc)

    in_shardings = tuple(
        _map_specs(mesh, rules, spec, shapes)
        for spec, shapes in zip(cell.arg_specs, cell.args))
    out_shardings = None
    if cell.out_specs is not None:
        out_shapes = jax.eval_shape(cell.step_fn, *cell.args)
        out_shardings = tuple(
            None if spec is None else _map_specs(mesh, rules, spec, shapes)
            for spec, shapes in zip(cell.out_specs, out_shapes))

    t0 = time.time()
    with jax.set_mesh(mesh), shd.activation_rules(mesh, rules):
        jitted = jax.jit(cell.step_fn, in_shardings=in_shardings,
                         out_shardings=out_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo_text = compiled.as_text()
    if os.environ.get("REPRO_DUMP_HLO"):
        with open(os.environ["REPRO_DUMP_HLO"], "w") as f:
            f.write(hlo_text)
    report = roofline.analyze(
        compiled, hlo_text, arch=arch, shape=shape_name,
        mesh_name=mesh_name, chips=chips, model_flops=cell.model_flops)
    out = report.to_json()
    out.update({"status": "ok", "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1)})
    try:
        ma = compiled.memory_analysis()
        out["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(ma, k)}
    except Exception:
        out["memory_analysis"] = None
    if verbose:
        t = report.terms()
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compute={t.compute_s * 1e3:.2f}ms memory={t.memory_s * 1e3:.2f}ms "
              f"collective={t.collective_s * 1e3:.2f}ms dominant={t.dominant} "
              f"roofline_frac={out['roofline_fraction']:.3f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    del compiled, lowered, jitted
    gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            try:
                result = run_cell(arch, shape_name, multi_pod=mp)
            except Exception as e:  # a failed cell is a bug — record it
                traceback.print_exc()
                result = {"arch": arch, "shape": shape_name,
                          "mesh": "2x16x16" if mp else "16x16",
                          "status": "error", "error": repr(e)}
                failures += 1
            with open(path, "w") as f:
                json.dump(result, f, indent=2)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
