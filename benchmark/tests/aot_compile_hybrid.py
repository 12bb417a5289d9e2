"""Compile a hybrid-decoder cell's state initialisation, train step AND plain
reference for the described chip (v5e), here, with no chip attached, and print
each one's argument, output and temporary bytes: what the TPU compiler would
refuse (a program that does not fit the chip's 16 GB) costs no chip time.
Nothing runs; a compile that passes is not a run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile_hybrid.py \\
        nemotron3_super_120b_a12b dense_staged [step|reference|both] [hlo.txt]

As ``aot_compile_lm.py`` it steers the one function by which the program asks
``jax.default_backend()`` whether to take its flash-attention kernel.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

import run  # noqa: E402
from tpu_compressed_dp.ops import ring_attention  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
ring_attention.use_fused_attention = ring_attention.fused_attention_fits

config, traffic = sys.argv[1:3]
what = sys.argv[3] if len(sys.argv) > 3 else "both"
cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
tr = json.load(open(traffic if os.path.exists(traffic)
                    else os.path.join(BENCH, "traffic", traffic + ".json")))
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
devices = list(topo.devices)[:tr["chips"]]
model = run.load_module(cfg["reference"])


def report(name, compiled, t0):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"{name} compiles ({time.time() - t0:.0f} s): temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB (aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f}), together {live / 1e9:.2f} GB "
          f"a device; {text.count('tpu_custom_call')} Pallas calls, "
          f"{text.count(' while(')} loops", flush=True)
    return text


if what in ("step", "both"):
    prog = run.load_module(cfg["program"]).build(cfg, tr, devices, model)
    t0 = time.time()
    lowered = prog.make_state.lower(0)
    lowered.compile()
    print(f"make_state compiles ({time.time() - t0:.0f} s)", flush=True)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        lowered.out_info)
    dat = NamedSharding(prog.mesh, P("data", "seq"))
    tokens = jax.ShapeDtypeStruct((prog.global_batch, cfg["seq_len"]), jnp.int32,
                                  sharding=dat)
    t0 = time.time()
    text = report("train step", jax.jit(prog.train_step, donate_argnums=0).lower(
        state, {"input": tokens, "target": tokens}).compile(), t0)
    if len(sys.argv) > 4:
        with open(sys.argv[4], "w") as f:
            f.write(text)

if what in ("reference", "both"):
    one = SingleDeviceSharding(devices[0])
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one),
        model.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    tokens = jax.ShapeDtypeStruct((cfg["per_chip_batch"], cfg["seq_len"]),
                                  jnp.int32, sharding=one)
    t0 = time.time()
    report("float32 reference", model.make_loss_and_grad(cfg).lower(
        params, tokens, tokens).compile(), t0)
