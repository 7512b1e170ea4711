"""Rehearsal 3 of the on-chip-measurement guide: compile a training
cell's real step program at full size for a DESCRIBED v5e:2x2 (no chip
attached) and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 benchmarks/tools/compile_full_size.py <workload> [rows_per_chip]

It builds the Trainer's own jitted step (`_build_train_step`) over a
mesh of described devices. Nothing runs: a compile that passes is not a
chip run and is never reported as one.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str, rows_per_chip=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    from benchmarks.lib import manifest, traffic
    from benchmarks.lib.jobs import train_fit
    from fengshen_tpu.parallel import make_mesh, set_mesh
    from fengshen_tpu.parallel.mesh import MeshConfig
    from fengshen_tpu.trainer import Trainer
    from fengshen_tpu.trainer.modules import CausalLMModule
    from fengshen_tpu.trainer.train_state import state_shardings

    jax.config.update("jax_enable_compilation_cache", False)
    man = manifest.load()
    cell = manifest.cell(man, workload)
    config = manifest.config_of(man, cell)
    mix = traffic.load_mix(cell["traffic"])
    chips = cell["chips"]
    rows = int(rows_per_chip or mix["rows_per_chip"]) * chips
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ctx = {"config": config}
    args = train_fit._args(ctx, "/tmp/unused_fit_dir", rows)
    trainer = Trainer(args)
    mesh = make_mesh(MeshConfig.from_argparse_args(args),
                     devices=topo.devices[:chips])
    trainer.mesh = mesh
    set_mesh(mesh)
    model, model_cfg = manifest.family(config).build(config)
    module = CausalLMModule(args, model, model_cfg)
    init_fn = trainer._make_init_fn(module, jax.random.PRNGKey(0), 1000)
    abstract = jax.eval_shape(init_fn)
    state_sh = state_shardings(module.partition_rules(), abstract, mesh)
    sample = {"input_ids": np.zeros((rows, mix["seq"]), np.int32)}
    step_fn, batch_sh = trainer._build_train_step(
        module, state_sh, module.batch_spec(sample), sample)
    with_sh = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, state_sh)
    batch = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        sample, batch_sh)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    compiled = step_fn.lower(with_sh, batch, rng).compile()
    m = compiled.memory_analysis()
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(abstract.params))
    print(f"{workload}: {n_params / 1e6:.1f} M parameters, {rows} rows x "
          f"{mix['seq']} a step on {chips} described v5e chip(s)")
    print(f"  per device: arguments {m.argument_size_in_bytes / 1e9:.2f} GB,"
          f" outputs {m.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB; arguments + temporaries "
          f"{(m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9:.2f} GB"
          f" of 16.91 GB")
    text = compiled.as_text()
    for op in ("all-gather", "reduce-scatter", "all-reduce"):
        print(f"  {op}: {text.count(op + '(') + text.count(op + '-start(')}"
              " in the compiled program")


if __name__ == "__main__":
    main(*sys.argv[1:])
