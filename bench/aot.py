#!/usr/bin/env python3
"""Compile a cell's largest step programs for a described TPU v5e chip, on
the host, and print their ``memory_analysis`` (no chip needed).

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell>

It compiles the widest bucket's decode step and longest prefill chunk, the
programs whose temporaries are largest, beside the resident weights and
state arena.  A compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import files, program
    from repro.core.hybrid import HybridKernel
    from repro.models import params as pm
    from repro.models.transformer import param_specs
    from repro.partition import DATA, MODEL, plan_for_mesh
    from repro.serve.decode import (PagedKV, make_decode_body,
                                    make_prefill_chunk_body)
    from repro.serve.state import layer_state_specs

    jax.config.update("jax_enable_compilation_cache", False)
    # the program refuses compiled kernels when the default backend is not
    # a TPU; here the target is a described chip, so say which to build
    import repro.models.ssm as ssm_mod
    import repro.serve.decode as decode_mod

    def compiled_kernels(backend):
        return (backend != "jnp", False)
    ssm_mod.resolve_kernel_backend = decode_mod.resolve_kernel_backend = \
        compiled_kernels
    cell = files.cell(args.workload)
    conf = files.config(cell["config"])
    cfg = program.model_config(conf, False)
    ec = program.engine_config(conf, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), (DATA, MODEL))
    plan = plan_for_mesh(mesh)
    B = ec.buckets[-1]
    T = ec.s_max // ec.block_pos_stride
    n_blocks = ec.n_kv_blocks or B * T
    paged = PagedKV(n_blocks=n_blocks, block_pos_stride=ec.block_pos_stride)
    sspecs = layer_state_specs(cfg, plan, stride=ec.block_pos_stride)
    sh = lambda spec: NamedSharding(mesh, spec)
    specs = param_specs(cfg, 1, 1, preskew=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh(s.pspec)),
        specs, is_leaf=lambda x: isinstance(x, pm.ParamSpec))
    arena = jax.tree.map(
        lambda sd, spec: jax.ShapeDtypeStruct(sd.shape, sd.dtype,
                                              sharding=sh(spec)),
        sspecs.arena_specs(n_blocks, ec.n_dense_slots or B
                           if sspecs.has_dense else 1),
        sspecs.arena_pspecs())
    i32 = lambda shape, spec: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                   sharding=sh(spec))
    lead = DATA
    ops = ([i32((B, T), P(lead, None))] if sspecs.has_paged else []) \
        + ([i32((B,), P(lead))] if sspecs.has_dense else [])
    out = {"cell": args.workload, "bucket": B}
    for chunk in (0, max(ec.prefill_chunks)):
        if chunk == 0:
            body, ins, outs, _, pctx = make_decode_body(
                cfg, mesh, plan, batch=B, s_max=ec.s_max, mode="gemv",
                per_slot=True, paged=paged, kernel_backend=ec.kernel_backend)
            head = [i32((B,), P(lead)), i32((B,), P(lead))]
            name = f"serve_step_bs{B}"
        else:
            body, ins, outs, _, pctx = make_prefill_chunk_body(
                cfg, mesh, plan, batch=B, s_max=ec.s_max, chunk=chunk,
                paged=paged, kernel_backend=ec.kernel_backend)
            head = [i32((B, chunk), P(lead, None)), i32((B,), P(lead)),
                    i32((B,), P(lead))]
            name = f"prefill_bs{B}_len{chunk}"
        k = HybridKernel(lambda grid, *a: body(*a), grid=pctx.grid,
                         in_specs=ins, out_specs=outs, name=name,
                         donate=(1,))
        c = k.bind(mesh).lower(params, arena, *head, *ops).compile()
        m = c.memory_analysis()
        out[name] = {f: int(getattr(m, f)) for f in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
        print(json.dumps({name: out[name]}), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
