"""Compile a cell's prefill and decode-round programs at full size for a
described TPU v5e, without a chip, and print their memory analysis.

    JAX_PLATFORMS=cpu python bench/aot.py --workload granite-3-2b.topk.batch

A rehearsal run by hand before chip time: Mosaic and XLA refuse here what
interpret mode never checks (tiling, VMEM, a program that does not fit).
Nothing is allocated at full size: parameters, cache and plan are shapes.
"""
import argparse
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--round-tokens", type=int, default=None,
                    help="compile the decode round at this length instead")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec
    from bench.serve import program_config
    from bench.traffic.common import prompt_length
    from bench.weights import served_params
    from repro.models import build_model
    from repro.serving import ServeEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    e = dict(cell.engine)
    if args.round_tokens:
        e["round_tokens"] = args.round_tokens
    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
                            tree)

    ref = spec.reference_module(cell)
    dm = ref.dims(cell.cfg)
    model = build_model(program_config(cell.cfg, ref))
    params = shaped(jax.eval_shape(lambda: served_params(ref.leaves, dm, 0)))
    slots, max_seq = int(e["slots"]), int(e["max_seq"])
    # the engine keeps only references to what it is given at wbits 16
    eng = ServeEngine(model, params, max_seq=8, batch_size=1,
                      sparsity=float(e["sparsity"]), method=e["method"],
                      plan_refresh_interval=int(e["plan_refresh_interval"]),
                      cache_mb=float(e["cache_mb"]), backend=e["backend"],
                      wbits=int(e["wbits"]))
    eng.max_seq = max_seq
    if eng.sparse_ctx is not None and eng.sparse_ctx.backend.is_kernel:
        eng.sparse_ctx.backend = dataclasses.replace(eng.sparse_ctx.backend,
                                                     interpret=False)

    def cache_shape():
        c = model.init_cache(slots, max_seq)
        c["length"] = jnp.zeros((slots,), jnp.int32)
        return c

    cache = shaped(jax.eval_shape(cache_shape))
    plan = shaped(jax.eval_shape(eng._init_plan))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one)
    programs = {f"decode_round[{e['round_tokens']}]":
                eng._decode_scan.lower(params, tok, cache, int(e["round_tokens"]), plan)}
    prompt = cell.traffic["prompt"]
    frames = prompt["frames"] if isinstance(prompt["frames"], list) else [prompt["frames"]]
    for k in frames:
        n = prompt_length(prompt, k)
        batch = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one)}
        programs[f"prefill[{n}]"] = eng._prefill_one.lower(params, batch)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"{args.workload} {name}: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.3f} GB, "
              f"aliased {ma.alias_size_in_bytes / 1e9:.3f} GB, "
              f"Mosaic kernels {kernels}", flush=True)


if __name__ == "__main__":
    main()
