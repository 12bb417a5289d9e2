"""Compile a cell's state initialisation and train step for the described
chip (v5e:2x2), here, with no chip attached: what the TPU compiler would
refuse costs no chip time.  Nothing runs; a compile that passes is not a run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile.py resnet50_imagenet benchmark/tests/data/topk_lw_staged_w4.json

The program asks ``jax.default_backend()`` whether to take its Pallas kernels
and sees the CPU here, so this script steers that one function, as the
on-chip-measurement guide says a scratch script may.
"""

import dataclasses
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import run  # noqa: E402
from tpu_compressed_dp.ops import kernels  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
kernels._dispatch_to_pallas = lambda n: (
    kernels._MODE != "off" and n >= kernels.MIN_PALLAS_ELEMS)

config, traffic = sys.argv[1:3]
cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
tr = json.load(open(traffic if os.path.exists(traffic)
                    else os.path.join(BENCH, "traffic", traffic + ".json")))
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
prog = run.load_module(cfg["program"]).build(
    cfg, tr, list(topo.devices)[:tr["chips"]], run.load_module(cfg["reference"]))

t0 = time.time()
prog.make_state.lower(0).compile()
print(f"make_state compiles ({time.time() - t0:.0f} s)", flush=True)

rep, dat = NamedSharding(prog.mesh, P()), NamedSharding(prog.mesh, P("data"))
shaped = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
state = jax.eval_shape(prog.make_state, 0)
state = dataclasses.replace(
    jax.tree.map(lambda a: shaped(a, rep), state),
    ef=jax.tree.map(lambda a: shaped(a, dat), state.ef),
    comp=jax.tree.map(lambda a: shaped(a, dat), state.comp))
size = cfg["image_size"]
batch = {"input": jax.ShapeDtypeStruct((prog.global_batch, size, size, 3),
                                       jnp.uint8, sharding=dat),
         "target": jax.ShapeDtypeStruct((prog.global_batch,), jnp.int32,
                                        sharding=dat)}
t0 = time.time()
compiled = jax.jit(prog.train_step, donate_argnums=0).lower(state, batch).compile()
mem = compiled.memory_analysis()
text = compiled.as_text()
print(f"train step compiles ({time.time() - t0:.0f} s): temporaries "
      f"{mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
      f"{mem.argument_size_in_bytes / 1e9:.2f} GB a device; "
      f"{text.count('tpu_custom_call')} Pallas calls, "
      f"{text.count(' all-gather')} all-gathers")
