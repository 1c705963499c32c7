"""Layer (module) system. Reference: python/paddle/nn/layer/layers.py (`nn.Layer`).

TPU-native twist: alongside the stateful paddle API (state_dict / parameters / __call__),
every Layer supports *functional application* — `layer.functional_call(params, *args)`
swaps parameter payloads for tracers, enabling `jax.jit`/`grad`/`shard_map` over whole
models. That is the compiled training-step path; the stateful path is eager ergonomics.
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.lockwitness import make_rlock
from ..framework import dtype as _dt
from ..framework.lazy_init import LazyGuard
from ..tensor import Tensor
from . import initializer as I

# functional_call rebinds parameter payloads IN PLACE for the length of one
# forward (a trace, under jit). Threads that share a model — fleet replicas
# warming the same step programs — must neither trace at once nor snapshot
# the state meanwhile: they would read, cast and cache the other thread's
# tracers. One lock for every layer (a per-layer lock made lazily would
# itself race); it is held while tracing a forward, never across a compile.
_BIND_LOCK = make_rlock("layer._BIND_LOCK")


class ParamAttr:
    """Reference: python/paddle/base/param_attr.py."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if attr is False:
            return False
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"cannot convert {attr!r} to ParamAttr")


class Parameter(Tensor):
    """A trainable Tensor (stop_gradient=False by default)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip", "is_distributed")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False
        self.persistable = True


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = _dt.convert_dtype(dtype)
        self._parameters: dict[str, Parameter] = collections.OrderedDict()
        self._sub_layers: dict[str, Layer] = collections.OrderedDict()
        self._buffers: dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------------------------------------------------------------ attribute magic
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning parameters")
            params[name] = value
            layers and layers.pop(name, None)
            buffers and buffers.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() before assigning sublayers")
            layers[name] = value
            params and params.pop(name, None)
            buffers and buffers.pop(name, None)
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                buffers[name].set_value(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # ------------------------------------------------------------------ construction api
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = _dt.convert_dtype(dtype) or self._dtype or _dt.get_default_dtype()
        init = attr.initializer or default_initializer
        if init is None:
            glob = I.get_global_initializer()
            if glob is not None:
                init = glob[1] if (is_bias and glob[1] is not None) else glob[0]
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        if LazyGuard.active():      # a shape and a dtype, no bytes
            value = jax.ShapeDtypeStruct(tuple(int(n) for n in shape),
                                         jnp.dtype(dtype))
        else:
            value = init(shape, dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        if attr.learning_rate != 1.0:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ------------------------------------------------------------------ traversal
    def parameters(self, include_sublayers=True) -> list[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True, include_self=True):
        seen = set()
        for name, layer in self._traverse(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (f"{name}.{pname}" if name else pname), p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self._traverse(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (f"{name}.{bname}" if name else bname), b

    def _traverse(self, prefix="", include_sublayers=True):
        yield prefix, self
        if include_sublayers:
            for lname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from sub._traverse(sub_prefix, True)

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def sublayers(self, include_self=False):
        out = []
        for name, l in self._traverse("", True):
            if l is self and not include_self:
                continue
            out.append(l)
        return out

    def named_sublayers(self, prefix="", include_self=False):
        for name, l in self._traverse(prefix, True):
            if l is self and not include_self:
                continue
            yield name, l

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ------------------------------------------------------------------ mode/cast
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_params(_dt.convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._cast_params(_dt.convert_dtype(dtype))
        return self

    def float(self):
        return self.astype(_dt.float32)

    def half(self):
        return self.astype(_dt.float16)

    def bfloat16(self):
        return self.astype(_dt.bfloat16)

    def _cast_params(self, dtype):
        for l in self.sublayers(include_self=True):
            l._dtype = dtype
            for p in l._parameters.values():
                if p is not None and jnp.issubdtype(p.dtype, jnp.floating):
                    p._value = p._value.astype(dtype)
            for b in l._buffers.values():
                if b is not None and jnp.issubdtype(b.dtype, jnp.floating):
                    b._value = b._value.astype(dtype)

    # ------------------------------------------------------------------ state dict
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="",
                   use_hook=True):
        out = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip("."),
                                             include_sublayers=include_sublayers):
            out[name] = p
        for name, layer in self._traverse(structured_name_prefix.rstrip("."), include_sublayers):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                out[f"{name}.{bname}" if name else bname] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                own[k].set_value(v.numpy() if isinstance(v, Tensor) else np.asarray(v))
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ------------------------------------------------------------------ hooks
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        hid = self._hook_id
        self._forward_pre_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        hid = self._hook_id
        self._forward_post_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_post_hooks, hid)

    # ------------------------------------------------------------------ call
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ------------------------------------------------------------------ functional path
    def raw_state(self):
        """pytree of raw jax arrays: {name: array} for params + persistable buffers."""
        with _BIND_LOCK:
            return {k: v._value for k, v in self.state_dict().items()}

    def load_raw_state(self, raw):
        sd = self.state_dict()
        for k, v in raw.items():
            if k in sd:
                sd[k]._value = v

    def functional_call(self, raw_state: dict, *args, _capture_mutations=None, **kwargs):
        """Run forward with parameter payloads replaced by `raw_state` values (tracers
        allowed). Restores original payloads afterwards. This is what jit/grad close
        over — the TPU-native compiled path.

        `_capture_mutations`: optional dict filled with {name: new_value} for state
        entries the forward reassigned in place (batch-norm running mean/var). The
        compiled TrainStep threads these out as aux outputs so running statistics
        survive the restore below."""
        with _BIND_LOCK:
            sd = self.state_dict()
            saved = {k: t._value for k, t in sd.items()}
            saved_sg = {k: t.stop_gradient for k, t in sd.items()}
            try:
                for k, v in raw_state.items():
                    if k in sd:
                        sd[k]._value = v
                        sd[k].stop_gradient = True  # tape off inside functional path
                out = self(*args, **kwargs)
                if _capture_mutations is not None:
                    for k, t in sd.items():
                        set_to = raw_state.get(k, saved[k])
                        if t._value is not set_to:
                            _capture_mutations[k] = t._value
                return out
            finally:
                for k, t in sd.items():
                    t._value = saved[k]
                    t.stop_gradient = saved_sg[k]

    def clear_gradients(self, set_to_zero=False):
        for p in self.parameters():
            p.clear_gradient(set_to_zero)

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}" if extra else f"{type(self).__name__}("]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            lines.append(f"  ({name}): " + "\n  ".join(sub_repr))
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else lines[0] + ")"


class _HookRemoveHelper:
    def __init__(self, store, hid):
        self._store, self._hid = store, hid

    def remove(self):
        self._store.pop(self._hid, None)
