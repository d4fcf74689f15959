"""Compile the trainer's step for described TPU v5e chips.

  PYTHONPATH=src python tests/rehearse_v5e.py --arch mesh1k --batch 4 \
      --steps 3 [--data 1 --model 1] [--strategy uniform|auto] \
      [--bn-scope local|global]

The rehearsal before a chip call.  It takes the trainer's own flags
(`launch.train.parser`) and its own construction: `train.build` on a mesh
of described `v5e:2x2` devices gives the plan, loss, optimizer and batch
specs; `train.train_state` on a host-device mesh of the same shape gives
the state's layout, carried over to the described mesh; `train.train_step`
jits the step, which the TPU compiler installed on this host compiles.
Prints the compile seconds, `memory_analysis()` with the per-device peak
that must fit 16 GB, the state leaves whose output sharding differs from
their input's (each would recompile the step at step 1), and the
collective and Pallas-kernel counts of the compiled HLO.  Nothing runs,
so it says nothing about results or times.  About a minute per compile;
not a pytest test for that reason.  Runs on the CPU backend only: it
never takes a chip.
"""
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.launch import train  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.utils import human_bytes  # noqa: E402


def main():
    args = train.parser().parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shape = dict(data=args.data, model=args.model, pod=args.pod)
    mesh = make_mesh(**shape, devices=topo.devices)
    cfg, params, opt, loss, mk, _, prec, extras = train.build(args, mesh)
    host_state = train.train_state(params, opt, make_mesh(**shape))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, x.sharding.spec)),
        host_state)
    batch = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype,
        sharding=NamedSharding(mesh, extras["batch_spec"](k)))
        for k, v in mk(0).items()}
    step = train.train_step(args, opt, loss, prec, mesh, state)
    t0 = time.time()
    compiled = step.lower(*state, batch).compile()
    print(f"compiled {cfg.name} {dict(mesh.shape)} {args.strategy} batch "
          f"{args.batch} in {time.time() - t0:.1f}s (rehearsal compile, not "
          f"a chip run)")
    mem = compiled.memory_analysis()
    print(mem)
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"per-device peak {human_bytes(peak)} of 16 GB; "
          f"{human_bytes(mem.alias_size_in_bytes)} aliased to donated inputs")
    ins = jax.tree.leaves(compiled.input_shardings[0][:2])
    outs = jax.tree.leaves(compiled.output_shardings[:2])
    leaves = jax.tree.leaves(state[:2])
    moved = sum(not o.is_equivalent_to(i, x.ndim)
                for o, i, x in zip(outs, ins, leaves))
    print(f"state leaves whose output sharding differs from the input's: "
          f"{moved} of {len(leaves)}")
    text = compiled.as_text()
    for op in ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "tpu_custom_call"):
        print(f"  {op}: {text.count(op)}")


if __name__ == "__main__":
    main()
