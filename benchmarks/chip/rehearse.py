"""Compile a serving cell's programs for a described TPU v5e, no chip needed.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload <cell>

Lowers the programs the cell's window drives (the decode step and the
padded admission round at every prefill bucket the traffic reaches) at
the cell's sizes against the shapes of its weights, cache and decode
state, compiles them for one chip of a ``v5e:2x2`` topology, and prints
each program's ``memory_analysis`` (bytes of arguments, outputs,
temporaries, generated code).  What the chip's compiler refuses here costs
no chip time.  Nothing runs, so nothing here is a time.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    sys.path.insert(0, str(run.HERE))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models.model import Model
    from repro.serve.engine import _shared_steps, pow2_buckets

    jax.config.update("jax_enable_compilation_cache", False)
    driver = run.load_module(spec["driver"])
    cfg, traffic = spec["config"], spec["traffic"]
    dep = traffic["deployment"]
    n, max_len = dep["n_slots"], dep["max_len"]
    arch = driver.program_config(cfg)
    model = Model(arch)
    steps = _shared_steps(arch, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = shaped(model.abstract_params())
    cache = shaped(jax.eval_shape(
        lambda: model.init_cache(n, max_len, per_slot=True)))
    vec = {"i32": jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one),
           "bool": jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one)}
    state = {"tok": vec["i32"], "remaining": vec["i32"],
             "finished": vec["bool"], "eos": vec["i32"],
             "has_eos": vec["bool"]}
    engine_buckets = pow2_buckets(max_len)     # the engine's default
    buckets = sorted({next(b for b in engine_buckets if b >= L) for L in
                      driver.warm_lengths(traffic["prompt_len"],
                                          engine_buckets)})
    rows = []

    def report(name, lowered):
        mem = lowered.compile().memory_analysis()
        row = {"program": name,
               "argument_bytes": mem.argument_size_in_bytes,
               "output_bytes": mem.output_size_in_bytes,
               "temp_bytes": mem.temp_size_in_bytes,
               "code_bytes": mem.generated_code_size_in_bytes}
        rows.append(row)
        print(json.dumps(row), flush=True)

    report("decode", steps.decode.lower(params, cache, vec["i32"]))
    for b in buckets:
        toks = jax.ShapeDtypeStruct((n, b), jnp.int32, sharding=one)
        report(f"admit_packed[{b}]", steps.admit_packed.lower(
            params, cache, state, toks, vec["i32"], vec["i32"],
            vec["bool"], vec["i32"], vec["i32"], vec["i32"], vec["bool"],
            max_len))
    peak = max(r["argument_bytes"] + r["output_bytes"] + r["temp_bytes"]
               for r in rows)
    print(f"largest program footprint (arguments + outputs + temporaries): "
          f"{peak} bytes")


if __name__ == "__main__":
    main()
