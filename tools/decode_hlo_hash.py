"""Hash of a model's decode program as the TPU compiler builds it, to
show that a change leaves a model's compiled decode step unchanged.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/decode_hlo_hash.py \
        --arch internlm2-1.8b --slots 16 --max-len 3072

Compiles the engine's decode step (caches donated, the Pallas branch of
``kernels.ops``) for one chip of a described ``v5e:2x2``, nothing run,
and prints the sha256 of the compiled HLO text with its debug
information left out: Python tracebacks are kept out of locations, and
the file, function and stack-frame tables and every ``metadata={...}``
are dropped. What is left (instructions, shapes, layouts, schedule and
the kernels' bodies) changes only if the program does. The kernels'
bodies name their source files, so compare two trees from one path:

    cp -r <tree>/src /tmp/cmp/src
    PYTHONPATH=/tmp/cmp/src python tools/decode_hlo_hash.py ...
"""
import argparse
import hashlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_debug(text: str) -> str:
    out, table = [], False
    for line in text.splitlines():
        if line in TABLES:
            table = True
            continue
        if table and (re.match(r"^\d+ ", line) or not line.strip()):
            continue
        table = False
        out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.config import get_config
    from repro.kernels import ops
    from repro.models import model as lm

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    ops.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    cfg = get_config(args.arch)
    params = place(lm.param_shapes(cfg))
    caches = place(jax.eval_shape(lambda: lm.init_caches(
        cfg, args.slots, ops.cache_len(args.max_len))))

    def _decode(params, tokens, caches, pos):
        return lm.decode_step(params, cfg, tokens, caches, pos, scan=True)

    compiled = jax.jit(_decode, donate_argnums=(2,)).lower(
        params, jax.ShapeDtypeStruct((args.slots, 1), jnp.int32, sharding=one),
        caches, jax.ShapeDtypeStruct((args.slots,), jnp.int32, sharding=one),
    ).compile()
    print(hashlib.sha256(strip_debug(compiled.as_text()).encode()).hexdigest())


if __name__ == "__main__":
    main()
