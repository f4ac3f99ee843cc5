"""The one general traffic generator: a closed loop with one caller.

A traffic file gives the sizes (bytes of one rank's send buffer); the
configuration gives the operations. A round is every
operation at every size, in a fixed order, and a window is whole rounds,
so every run of a cell holds the same mix whatever the seed: the seed
draws the data and which call of each kind is compared, never the work.
"""

import numpy as np

from perfbench import manifest


def round_of(cell):
    """[(operation, bytes per rank), ...] of one round: every operation
    at the first size, then at the next."""
    return [(op, s) for s in cell["traffic"]["sizes_bytes"]
            for op in cell["operations"]]


def dtype_of(cfg, op):
    return np.dtype(cfg["dtype"].get(op, cfg["dtype"]["default"]))


def inputs_of(cell):
    """{(dtype name, elements per rank): [operations that send it]}: one
    buffer per distinct shape, the same buffers every iteration, as OSU's
    loops have them."""
    out = {}
    for op, size in round_of(cell):
        ops = out.setdefault(input_key(cell, op, size), [])
        if op not in ops:
            ops.append(op)
    return out


def input_key(cell, op, size):
    dt = dtype_of(cell["config"], op)
    n = cell["config"]["ranks"]
    return (dt.name, manifest.operation(op).elems(n, size, dt.itemsize))


def payload_bytes(cell, op, size):
    """OSU's message size of one call: the bytes of one rank's send
    buffer as sent (block operations round the element count up)."""
    name, elems = input_key(cell, op, size)
    return elems * np.dtype(name).itemsize


def make_inputs(seed, keys, n, sharding=None):
    """Every input of the cell in ONE jitted call, on the device(s), from
    the seed: (n, elements) each. float32 gets a random sign, 23 random
    mantissa bits and an exponent in 2**-7 .. 2**0, so no value is
    exactly representable in a lower precision and no sum overflows;
    int32 gets 32 random bits."""
    import jax
    import jax.numpy as jnp

    # --seed may pass 2**31: split it over the key's two words
    key = jax.random.wrap_key_data(jnp.asarray(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32))

    def gen(key):
        out = []
        for i, (name, elems) in enumerate(keys):
            bits = jax.random.bits(jax.random.fold_in(key, i), (n, elems),
                                   jnp.uint32)
            if name == "float32":
                expo = jnp.uint32(120) + ((bits >> 23) & jnp.uint32(7))
                bits = (bits & jnp.uint32(0x807FFFFF)) | (expo << 23)
            elif name != "int32":
                raise SystemExit(f"perfbench: no generator for {name}")
            out.append(jax.lax.bitcast_convert_type(bits, jnp.dtype(name)))
        return tuple(out)

    kw = {} if sharding is None else {
        "out_shardings": tuple(sharding for _ in keys)}
    return dict(zip(keys, jax.jit(gen, **kw)(key)))
