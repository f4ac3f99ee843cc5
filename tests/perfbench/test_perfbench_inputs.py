"""The inputs of every cell, byte for byte: ``traffic.make_inputs`` gives
each cell that has no operation with a ``make`` exactly what the generator
gave before operations could draw their own inputs (kept here as the
oracle, as it was), at toy sizes on a fixed seed. And the generator's
rules for an operation that does draw its own."""

import hashlib
import sys
import types

import numpy as np
import pytest

from perfbench import manifest, traffic

MAN = manifest.Manifest()
SEED = 2**33 + 2**31 + 12345  # over 32 bits: both words of the key count


def oracle_make_inputs(seed, keys, n, sharding=None):
    """The generator as it was before ``make``: every input of the cell in
    ONE jitted call, on the device(s), from the seed: (n, elements) each.
    float32 gets a random sign, 23 random mantissa bits and an exponent in
    2**-7 .. 2**0, so no value is exactly representable in a lower
    precision and no sum overflows; int32 gets 32 random bits."""
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


def digests(inputs):
    return {k: hashlib.sha256(np.ascontiguousarray(np.asarray(v)).tobytes())
            .hexdigest() for k, v in inputs.items()}


@pytest.mark.parametrize("cell", sorted(MAN.cells))
def test_every_cell_gets_the_inputs_it_got_before(cell):
    c = MAN.cell(cell, toy=True)
    keys = list(traffic.inputs_of(c))
    assert all(len(k) == 2 for k in keys)  # no operation of it has ``make``
    n = c["config"]["ranks"]
    got = traffic.make_inputs(SEED, keys, n, cfg=c["config"])
    assert list(got) == keys
    assert digests(got) == digests(oracle_make_inputs(SEED, keys, n))


def op_module(monkeypatch, name, make):
    mod = types.ModuleType(name)
    mod.KIND = "move"
    mod.elems = lambda n, nbytes, itemsize: max(1, nbytes // itemsize)
    mod.make = make
    monkeypatch.setitem(sys.modules, f"perfbench.ops.{name}", mod)
    return mod


def test_a_dtype_with_no_generator_needs_a_make(monkeypatch):
    import jax.numpy as jnp

    with pytest.raises(SystemExit, match="no generator for float16"):
        traffic.make_inputs(7, [("float16", 8)], 2)
    op_module(monkeypatch, "halves",
              lambda key, n, elems, cfg: jnp.full((n, elems), cfg["v"],
                                                  jnp.float16))
    got = traffic.make_inputs(7, [("float16", 8, "halves")], 2,
                              cfg={"v": 0.5})
    np.testing.assert_array_equal(
        np.asarray(got[("float16", 8, "halves")]),
        np.full((2, 8), 0.5, np.float16))


def test_a_make_of_another_shape_or_dtype_ends_the_run(monkeypatch):
    import jax.numpy as jnp

    op_module(monkeypatch, "short", lambda key, n, elems, cfg:
              jnp.zeros((n, elems - 1), jnp.int32))
    with pytest.raises(SystemExit, match="short.make gave"):
        traffic.make_inputs(7, [("int32", 8, "short")], 2)
    op_module(monkeypatch, "wide", lambda key, n, elems, cfg:
              jnp.zeros((n, elems), jnp.float32))
    with pytest.raises(SystemExit, match="wide.make gave"):
        traffic.make_inputs(7, [("int32", 8, "wide")], 2)


def test_a_made_input_is_the_operations_alone(monkeypatch):
    """An operation with ``make`` never shares a buffer with a uniform
    input of the same shape, and its key says whose it is; the uniform
    inputs beside it are the oracle's."""
    import jax

    op_module(monkeypatch, "drawn", lambda key, n, elems, cfg:
              jax.random.uniform(key, (n, elems)))
    cell = MAN.cell("osu_span2.small", toy=True)
    cell["operations"] = ["allreduce", "drawn"]
    keys = list(traffic.inputs_of(cell))
    sizes = len(cell["traffic"]["sizes_bytes"])
    # float32 both, one element count per size: two buffers a size
    assert [k[2:] for k in keys] == [(), ("drawn",)] * sizes
    assert [k[:2] for k in keys[::2]] == [k[:2] for k in keys[1::2]]
    got = traffic.make_inputs(SEED, keys, 2)
    for uniform, drawn in zip(keys[::2], keys[1::2]):
        assert not np.array_equal(np.asarray(got[uniform]),
                                  np.asarray(got[drawn]))
    # a uniform input is drawn from its own place among the keys, as before
    # (the oracle's stand-ins for the made ones are int32, of no account)
    stand_in = [k if len(k) == 2 else ("int32", k[1]) for k in keys]
    want = oracle_make_inputs(SEED, stand_in, 2)
    for k in keys[::2]:
        assert digests({k: got[k]}) == digests({k: want[k]})
    for op, size in traffic.round_of(cell):
        key = traffic.input_key(cell, op, size)
        assert key[2:] == (("drawn",) if op == "drawn" else ())
        assert traffic.payload_bytes(cell, op, size) == key[1] * 4


def test_a_made_input_is_drawn_on_the_cpu_whatever_the_device(monkeypatch):
    """A ``make`` that rounds floats (a normal, through erfinv) is drawn by
    XLA:CPU whatever device the rank computes on, so a chip rank and a
    host rank of a spanning cell hold the same rows: under two default
    devices the same bits, drawn on the CPU backend's first device, and
    put on the sharding where one is given."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    op_module(monkeypatch, "normal", lambda key, n, elems, cfg:
              jax.random.normal(key, (n, elems)) * cfg["scale"] + 1.0)
    keys = [("float32", 4096, "normal")]
    cfg = {"scale": 3.0}
    cpus = jax.local_devices(backend="cpu")
    assert len(cpus) >= 3
    got = []
    for dev in (cpus[0], cpus[-1]):
        with jax.default_device(dev):
            (x,) = traffic.make_inputs(SEED, keys, 2, cfg=cfg).values()
        assert x.devices() == {cpus[0]}
        got.append(np.asarray(x))
    np.testing.assert_array_equal(got[0], got[1])
    assert np.unique(got[0]).size > 8000  # floats, not a constant
    sharding = NamedSharding(Mesh(np.array(cpus[1:3]), ("rank",)),
                             P("rank"))
    (y,) = traffic.make_inputs(SEED, keys, 2, sharding, cfg=cfg).values()
    assert y.sharding == sharding
    np.testing.assert_array_equal(np.asarray(y), got[0])
