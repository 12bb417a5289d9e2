"""Compile an LM cell's state initialisation and train step for the described
chip (v5e:2x2), here, with no chip attached: what the TPU compiler would
refuse (a kernel, a program that does not fit) costs no chip time.  Nothing
runs; a compile that passes is not a run.

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_compile_lm.py ouro_2p6b dense_staged

The program asks ``jax.default_backend()`` whether to take its flash-attention
kernel and sees the CPU here, so this script steers that one function, as the
on-chip-measurement guide says a scratch script may.
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import run  # noqa: E402
from tpu_compressed_dp.ops import ring_attention  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
ring_attention.use_fused_attention = ring_attention.fused_attention_fits

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

# every leaf's sharding as make_state lays it out
out = prog.make_state.lower(0).out_info
state = jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), out)
dat = NamedSharding(prog.mesh, P("data", "seq"))
tokens = jax.ShapeDtypeStruct((prog.global_batch, cfg["seq_len"]), jnp.int32,
                              sharding=dat)
t0 = time.time()
compiled = jax.jit(prog.train_step, donate_argnums=0).lower(
    state, {"input": tokens, "target": tokens}).compile()
mem = compiled.memory_analysis()
text = compiled.as_text()
print(f"train step compiles ({time.time() - t0:.0f} s): temporaries "
      f"{mem.temp_size_in_bytes / 1e9:.2f} GB, arguments "
      f"{mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
      f"{mem.output_size_in_bytes / 1e9:.2f} GB (aliased "
      f"{mem.alias_size_in_bytes / 1e9:.2f}) a device; "
      f"{text.count('tpu_custom_call')} Pallas calls, "
      f"{text.count(' while(')} loops")
