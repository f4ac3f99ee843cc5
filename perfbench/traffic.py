"""The one general traffic generator: a closed loop with one caller.

A traffic file gives the sizes (bytes of one rank's send buffer); the
configuration gives the operations. A round is every
operation at every size, in a fixed order, and a window is whole rounds,
so every run of a cell holds the same mix whatever the seed: the seed
draws the data and which call of each kind is compared, never the work.

The data: uniform bits of the configuration's dtype, one buffer per
(dtype, elements), unless the operation draws its own inputs with
``make(key, n, elems, cfg)`` (a distribution the deployment states, such
as skewed keys); such an input is the operation's alone. The uniform
inputs of a cell come from one jitted program on its device(s), the made
ones from one on the CPU backend, both before the first warm-up call.
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
    """(dtype name, elements per rank), and the operation's name after
    them where the operation draws its own inputs (``make``)."""
    dt = dtype_of(cell["config"], op)
    n = cell["config"]["ranks"]
    mod = manifest.operation(op)
    key = (dt.name, mod.elems(n, size, dt.itemsize))
    return key + (op,) if hasattr(mod, "make") else key


def payload_bytes(cell, op, size):
    """OSU's message size of one call: the bytes of one rank's send
    buffer as sent (block operations round the element count up)."""
    name, elems = input_key(cell, op, size)[:2]
    return elems * np.dtype(name).itemsize


def make_inputs(seed, keys, n, sharding=None, cfg=None):
    """Every input of the cell from the seed: (n, elements) each, input i
    from ``fold_in(key, i)``. The uniform ones come from ONE jitted call
    on the device(s): float32 gets a random sign, 23 random mantissa bits
    and an exponent in 2**-7 .. 2**0, so no value is exactly representable
    in a lower precision and no sum overflows; int32 gets 32 random bits.
    Integer work and a bitcast alone, so every backend gives the same
    bits. A key that names an operation is that operation's ``make(key,
    n, elems, cfg)``, which has to give exactly that shape and dtype; the
    made ones come from one more jitted call, always on the CPU backend:
    a ``make`` may round floats, and XLA:TPU and XLA:CPU need not round
    alike, so this is how a chip rank and a host rank of a spanning cell
    draw the same rows. They are then put on ``sharding``, or left in host
    memory where none is given (a spanning rank puts its own rows on its
    device)."""
    import jax
    import jax.numpy as jnp

    # --seed may pass 2**31: split it over the key's two words
    key = jax.random.wrap_key_data(jnp.asarray(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], jnp.uint32))
    uniform = [(i, k) for i, k in enumerate(keys) if len(k) == 2]
    made = [(i, k) for i, k in enumerate(keys) if len(k) > 2]

    def gen(key):
        out = []
        for i, (name, elems) in uniform:
            bits = jax.random.bits(jax.random.fold_in(key, i), (n, elems),
                                   jnp.uint32)
            if name == "float32":
                expo = jnp.uint32(120) + ((bits >> 23) & jnp.uint32(7))
                bits = (bits & jnp.uint32(0x807FFFFF)) | (expo << 23)
            elif name != "int32":
                raise SystemExit(f"perfbench: no generator for {name}")
            out.append(jax.lax.bitcast_convert_type(bits, jnp.dtype(name)))
        return tuple(out)

    def draw(key):
        out = []
        for i, (name, elems, op) in made:
            x = manifest.operation(op).make(jax.random.fold_in(key, i), n,
                                            elems, cfg)
            if x.shape != (n, elems) or x.dtype != jnp.dtype(name):
                raise SystemExit(f"perfbench: {op}.make gave {x.dtype} "
                                 f"{x.shape}, not {name} {(n, elems)}")
            out.append(x)
        return tuple(out)

    got = {}
    if uniform:
        kw = {} if sharding is None else {
            "out_shardings": tuple(sharding for _ in uniform)}
        got.update(zip((k for _, k in uniform), jax.jit(gen, **kw)(key)))
    if made:
        cpu = jax.local_devices(backend="cpu")[0]
        for (_, k), x in zip(made, jax.jit(draw)(jax.device_put(key, cpu))):
            got[k] = x if sharding is None else jax.device_put(x, sharding)
    return {k: got[k] for k in keys}
