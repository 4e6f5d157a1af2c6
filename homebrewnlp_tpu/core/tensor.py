"""Named tensors over jax.numpy.

The TPU-native substrate replacing Mesh-TensorFlow tensors and the reference's
wrapper layer (/root/reference/src/mtf_wrapper.py, src/utils_mtf.py).  A
``NamedTensor`` is a jax array plus a tuple of ``Dim``s; dim names drive
einsum contraction, broadcasting, reductions and sharding annotations.  All
ops are pure jnp — autodiff is native ``jax.grad`` (the reference needed a
hand-written reverse sweep, src/optimizer/__init__.py:143-174, because mtf
lacked tracing AD).
"""
from __future__ import annotations

import dataclasses
import string
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .dims import (DIM_LIST, Dim, SHAPE, deduplicate, dim_name, index_of,
                   shape_size, shape_sub)

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NamedTensor:
    data: Array
    dims: typing.Tuple[Dim, ...]

    def __post_init__(self):
        object.__setattr__(self, "dims", tuple(self.dims))

    def tree_flatten(self):
        return (self.data,), self.dims

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    # -- convenience -------------------------------------------------------
    @property
    def shape(self) -> typing.Tuple[Dim, ...]:
        return self.dims

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def size(self) -> int:
        return shape_size(self.dims)

    def dim(self, name: typing.Union[str, Dim]) -> Dim:
        return self.dims[index_of(self.dims, name)]

    def axis(self, name: typing.Union[str, Dim]) -> int:
        return index_of(self.dims, name)

    def __repr__(self):
        return f"NamedTensor({list(self.dims)}, {self.data.dtype})"

    # arithmetic sugar
    def __add__(self, other):
        return add(self, other)

    def __radd__(self, other):
        return add(other, self)

    def __sub__(self, other):
        return subtract(self, other)

    def __rsub__(self, other):
        return subtract(other, self)

    def __mul__(self, other):
        return multiply(self, other)

    def __rmul__(self, other):
        return multiply(other, self)

    def __truediv__(self, other):
        return divide(self, other)

    def __rtruediv__(self, other):
        return divide(other, self)

    def __neg__(self):
        return unary(jnp.negative, self)


NT = NamedTensor
TensorLike = typing.Union[NT, float, int, Array]


def nt(data: Array, dims: SHAPE) -> NT:
    dims = tuple(dims)
    assert tuple(data.shape) == tuple(d.size for d in dims), (data.shape, dims)
    return NamedTensor(data, dims)


def zeros(dims: SHAPE, dtype=jnp.float32) -> NT:
    return nt(jnp.zeros([d.size for d in dims], dtype), dims)


def ones(dims: SHAPE, dtype=jnp.float32) -> NT:
    return nt(jnp.ones([d.size for d in dims], dtype), dims)


def zeros_like(t: NT) -> NT:
    return nt(jnp.zeros_like(t.data), t.dims)


def ones_like(t: NT) -> NT:
    return nt(jnp.ones_like(t.data), t.dims)


def constant(value: float, dtype=jnp.float32) -> NT:
    return nt(jnp.asarray(value, dtype), ())


def cast(t: NT, dtype) -> NT:
    return nt(t.data.astype(dtype), t.dims)


def stop_gradient(t: NT) -> NT:
    return nt(jax.lax.stop_gradient(t.data), t.dims)


# -- einsum ---------------------------------------------------------------

def _symbols(all_dims: DIM_LIST) -> typing.Dict[Dim, str]:
    letters = string.ascii_letters
    if len(all_dims) > len(letters):
        raise ValueError("too many distinct dims for einsum")
    return {d: letters[i] for i, d in enumerate(all_dims)}


def einsum(inputs: typing.Sequence[NT], output_shape: SHAPE) -> NT:
    """Named einsum: dims shared by name+size contract unless in the output.

    Replaces /root/reference/src/mtf_wrapper.py einsum; maps directly to one
    MXU-friendly XLA dot/contraction.
    """
    inputs = list(inputs)
    output_shape = list(output_shape)
    all_dims = deduplicate([d for t in inputs for d in t.dims] +
                           list(output_shape))
    sym = _symbols(all_dims)
    in_specs = ",".join("".join(sym[d] for d in t.dims) for t in inputs)
    out_spec = "".join(sym[d] for d in output_shape)
    dtype = jnp.result_type(*[t.dtype for t in inputs])
    # bf16 matmuls accumulate in f32 on the MXU; CPU's DotThunk can't emit
    # mixed bf16->f32 dots, so only request it on TPU backends
    prefer = None
    if dtype == jnp.bfloat16 and jax.default_backend() not in ("cpu",):
        prefer = jnp.float32
    data = jnp.einsum(f"{in_specs}->{out_spec}",
                      *[t.data for t in inputs],
                      preferred_element_type=prefer)
    return nt(data.astype(dtype), output_shape)


# -- broadcasting binary ops ---------------------------------------------

def _as_nt(x: TensorLike, like: typing.Optional[NT] = None) -> NT:
    if isinstance(x, NamedTensor):
        return x
    dtype = like.dtype if like is not None else jnp.float32
    return nt(jnp.asarray(x, dtype), ())


def _align(t: NT, out_dims: DIM_LIST) -> Array:
    """View of t.data transposed/expanded to out_dims order (size-1 on missing)."""
    perm = [t.axis(d) for d in out_dims if d in t.dims]
    data = jnp.transpose(t.data, perm) if perm != list(range(len(perm))) else t.data
    shape = [d.size if d in t.dims else 1 for d in out_dims]
    return jnp.reshape(data, shape)


def binary(op, a: TensorLike, b: TensorLike) -> NT:
    a = _as_nt(a, b if isinstance(b, NamedTensor) else None)
    b = _as_nt(b, a)
    out_dims = deduplicate(list(a.dims) + list(b.dims))
    return nt(op(_align(a, out_dims), _align(b, out_dims)), out_dims)


def add(a, b):
    return binary(jnp.add, a, b)


def subtract(a, b):
    return binary(jnp.subtract, a, b)


def multiply(a, b):
    return binary(jnp.multiply, a, b)


def divide(a, b):
    return binary(jnp.divide, a, b)


def maximum(a, b):
    return binary(jnp.maximum, a, b)


def minimum(a, b):
    return binary(jnp.minimum, a, b)


def mod(a, b):
    return binary(jnp.mod, a, b)


def floordiv(a, b):
    return binary(jnp.floor_divide, a, b)


def pow_(a, b):
    return binary(jnp.power, a, b)


def _cmp(op):
    def fn(a, b, dtype=None):
        out = binary(op, a, b)
        return cast(out, dtype) if dtype is not None else out
    return fn


greater_equal = _cmp(jnp.greater_equal)
greater = _cmp(jnp.greater)
less = _cmp(jnp.less)
less_equal = _cmp(jnp.less_equal)
equal = _cmp(jnp.equal)
not_equal = _cmp(jnp.not_equal)


def weighted_add(left: TensorLike, right: TensorLike, alpha: TensorLike) -> NT:
    """left * alpha + right * (1 - alpha) (reference: src/utils_mtf.py:332)."""
    return add(multiply(left, alpha), multiply(right, subtract(1, alpha)))


# -- unary ----------------------------------------------------------------

def unary(op, t: NT) -> NT:
    return nt(op(t.data), t.dims)


def exp(t):
    return unary(jnp.exp, t)


def log(t):
    return unary(jnp.log, t)


def sqrt(t):
    return unary(jnp.sqrt, t)


def rsqrt(t):
    return unary(jax.lax.rsqrt, t)


def square(t):
    return unary(jnp.square, t)


def reciprocal(t):
    return unary(jnp.reciprocal, t)


def negative(t):
    return unary(jnp.negative, t)


def sign(t):
    return unary(jnp.sign, t)


def abs_(t):
    return unary(jnp.abs, t)


def sigmoid(t):
    return unary(jax.nn.sigmoid, t)


def tanh(t):
    return unary(jnp.tanh, t)


def softplus(t):
    return unary(jax.nn.softplus, t)


def sin(t):
    return unary(jnp.sin, t)


def relu(t):
    return unary(jax.nn.relu, t)


def rsqrt_eps(t: NT, epsilon: float = 1e-6) -> NT:
    return rsqrt(add(t, epsilon))


# -- reductions -----------------------------------------------------------

def _reduce(op, t: NT, reduced_dim=None, output_shape=None) -> NT:
    if output_shape is None:
        if reduced_dim is None:
            output_shape = []
        else:
            output_shape = shape_sub(t.dims, reduced_dim)
    output_shape = list(output_shape)
    axes = tuple(i for i, d in enumerate(t.dims) if d not in output_shape)
    data = op(t.data, axis=axes) if axes else t.data
    # reorder remaining axes to match output_shape order
    remaining = [d for d in t.dims if d in output_shape]
    if remaining != output_shape:
        perm = [remaining.index(d) for d in output_shape]
        data = jnp.transpose(data, perm)
    return nt(data, output_shape)


def reduce_sum(t, reduced_dim=None, output_shape=None):
    return _reduce(jnp.sum, t, reduced_dim, output_shape)


def reduce_mean(t, reduced_dim=None, output_shape=None):
    return _reduce(jnp.mean, t, reduced_dim, output_shape)


def reduce_max(t, reduced_dim=None, output_shape=None):
    return _reduce(jnp.max, t, reduced_dim, output_shape)


def reduce_min(t, reduced_dim=None, output_shape=None):
    return _reduce(jnp.min, t, reduced_dim, output_shape)


def reduce_logsumexp(t, reduced_dim) -> NT:
    axis = t.axis(reduced_dim)
    return nt(jax.nn.logsumexp(t.data, axis=axis), shape_sub(t.dims, reduced_dim))


# -- shape ops ------------------------------------------------------------

def rename_dim(t: NT, old: typing.Union[str, Dim], new_name: str) -> NT:
    i = t.axis(old)
    dims = list(t.dims)
    dims[i] = Dim(new_name, dims[i].size)
    return nt(t.data, dims)


def replace_dim(t: NT, old: typing.Union[str, Dim], new: Dim) -> NT:
    i = t.axis(old)
    assert t.dims[i].size == new.size
    dims = list(t.dims)
    dims[i] = new
    return nt(t.data, dims)


def transpose_to(t: NT, dims: SHAPE) -> NT:
    dims = list(dims)
    perm = [t.axis(d) for d in dims]
    return nt(jnp.transpose(t.data, perm), dims)


def reshape(t: NT, new_dims: SHAPE) -> NT:
    """Order-preserving reshape (split/merge), mtf.reshape analogue."""
    new_dims = list(new_dims)
    assert shape_size(new_dims) == t.size, (t.dims, new_dims)
    return nt(jnp.reshape(t.data, [d.size for d in new_dims]), new_dims)


def slice_(t: NT, start: int, end: int, dim: typing.Union[str, Dim]) -> NT:
    """Slice along a named dim (reference: src/utils_mtf.py utils_slice).

    The reference anonymize->slice->unanonymize dance exists because mtf can't
    slice a sharded dim; under GSPMD a plain lax.slice is legal on any layout.
    """
    i = t.axis(dim)
    if start == 0 and end == t.dims[i].size:
        return t
    idx = [slice(None)] * len(t.dims)
    idx[i] = slice(start, end)
    dims = list(t.dims)
    dims[i] = Dim(dims[i].name, end - start)
    return nt(t.data[tuple(idx)], dims)


def concat(tensors: typing.Sequence[NT], dim: typing.Union[str, Dim]) -> NT:
    name = dim_name(dim)
    axis = index_of(tensors[0].dims, name)
    data = jnp.concatenate([t.data for t in tensors], axis=axis)
    dims = list(tensors[0].dims)
    dims[axis] = Dim(name, sum(t.dims[index_of(t.dims, name)].size for t in tensors))
    return nt(data, dims)


def pad(t: NT, dim: typing.Union[str, Dim], before: int, after: int, value=0.0) -> NT:
    i = t.axis(dim)
    widths = [(0, 0)] * len(t.dims)
    widths[i] = (before, after)
    dims = list(t.dims)
    dims[i] = Dim(dims[i].name, dims[i].size + before + after)
    return nt(jnp.pad(t.data, widths, constant_values=value), dims)


def unbind(t: NT, dim: typing.Union[str, Dim]) -> typing.List[NT]:
    """Split a dim into a list of tensors without it (src/utils_mtf.py unbind)."""
    i = t.axis(dim)
    dims = shape_sub(t.dims, t.dims[i])
    return [nt(jnp.take(t.data, j, axis=i), dims) for j in range(t.dims[i].size)]


def range_(dim: Dim, dtype=jnp.float32) -> NT:
    return nt(jnp.arange(dim.size, dtype=dtype), [dim])


def one_hot(t: NT, dim: Dim, dtype=jnp.float32) -> NT:
    return nt(jax.nn.one_hot(t.data, dim.size, dtype=dtype), list(t.dims) + [dim])


def cumsum(t: NT, dim: typing.Union[str, Dim]) -> NT:
    return nt(jnp.cumsum(t.data, axis=t.axis(dim)), t.dims)


def argmax(t: NT, reduced_dim) -> NT:
    axis = t.axis(reduced_dim)
    return nt(jnp.argmax(t.data, axis=axis), shape_sub(t.dims, t.dims[axis]))


def top_1(t: NT, reduced_dim) -> typing.Tuple[NT, NT]:
    axis = t.axis(reduced_dim)
    dims = shape_sub(t.dims, t.dims[axis])
    idx = jnp.argmax(t.data, axis=axis)
    val = jnp.max(t.data, axis=axis)
    return nt(val, dims), nt(idx, dims)


def gather_axis0(embedding: NT, indices: NT) -> NT:
    """out[idx..., emb_rest...] = embedding[indices[idx...], emb_rest...]

    jnp.take with native gradient replaces the reference's hand-written
    Gather/ScatterAdd mtf Operations (src/model/embedding.py:39-125).
    """
    out_dims = list(indices.dims) + list(embedding.dims[1:])
    return nt(jnp.take(embedding.data, indices.data, axis=0), out_dims)


def dropout(t: NT, train: bool, keep_prob: float, key: typing.Optional[Array]) -> NT:
    if not train or keep_prob >= 1.0 or key is None:
        return t
    mask = jax.random.bernoulli(key, keep_prob, t.data.shape)
    return nt(jnp.where(mask, t.data / keep_prob, 0).astype(t.dtype), t.dims)


def add_n(tensors: typing.Sequence[TensorLike]) -> NT:
    out = tensors[0]
    for t in tensors[1:]:
        out = add(out, t)
    return out


def to_np(t: NT) -> np.ndarray:
    return np.asarray(t.data)
