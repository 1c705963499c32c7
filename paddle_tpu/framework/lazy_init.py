"""`LazyGuard`: parameters made under it are abstract (nn/layer.py
`create_parameter` asks). A module of its own: `ops/parity.py` exports the
guard and `nn` imports `ops`."""
import threading


class LazyGuard:
    """Reference framework/LazyGuard: parameters made inside the guard are
    not initialised. Here they are ABSTRACT: `create_parameter` gives a
    Parameter whose payload is a `jax.ShapeDtypeStruct` (a shape and a
    dtype, no bytes on any device), to be filled through
    `state_dict()[key]._value` or `load_raw_state`. A model too large to
    hold twice (its initial leaves beside its checkpoint) is built so.
    Per thread, and re-entrant."""
    _local = threading.local()

    @classmethod
    def active(cls) -> bool:
        return getattr(cls._local, "depth", 0) > 0

    def __enter__(self):
        LazyGuard._local.depth = getattr(LazyGuard._local, "depth", 0) + 1
        return self

    def __exit__(self, *a):
        LazyGuard._local.depth -= 1
        return False
