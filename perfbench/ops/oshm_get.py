"""osu_oshm_get: PE 0 makes BLOCKING blocking gets in a row over PE 1's
symmetric allocation, block j from offset ``j * elems`` (each returns with
exactly what the range held), joins the values in the order of its calls on
its chip and sends the 4-byte notice. ``malloc`` gives zeros: PE 1 fills its
allocation once with its own row (a local ``put`` and a ``quiet`` in the
first warm-up call, then ``barrier_all``) and nothing writes it again. PE 1
waits for the notice and returns the head of its buffer."""

from perfbench.ops import _pt2pt, _shm

KIND = "move"


def elems(n, nbytes, itemsize):
    return _shm.BLOCKING * _pt2pt.elems(n, nbytes, itemsize)


def call(world, x, cfg):
    _shm.require(cfg)

    def fill(ctx, sym):
        if ctx.my_pe == _shm.TARGET:
            ctx.put(sym, x, ctx.my_pe, offset=0)
        ctx.barrier_all()  # its quiet completes the put

    ctx, sym, _, _ = _shm.allocation(world, ("oshm_get", x.shape[1]),
                                     x.shape[1], x.dtype, over=x, renew=fill)
    me = ctx.my_pe
    n = x.shape[1] // _shm.BLOCKING
    if me != _shm.TARGET:
        out = _shm.join([ctx.get(sym, _shm.TARGET, offset=j * n, nelems=n)
                         for j in range(_shm.BLOCKING)])
        _shm.notify(world, me, _shm.TARGET, _shm.head(x))
        return out
    _shm.notify(world, me, 1 - me)
    return _shm.head(x)


def expected(x, cfg, sums):
    return (lambda r: x[1][:1] if r == _shm.TARGET else x[1]), None


def least_bytes(n, s):
    # the chip writes what it got (s = BLOCKING blocks) to hold it
    return s, s
