"""Runtime kernel compilation — mx.rtc.

Parity surface: python/mxnet/rtc.py CudaModule (NVRTC runtime-compiled
CUDA, src/common/rtc.cc:35). The TPU analog of runtime kernel authorship
is Pallas: `PallasModule` compiles a kernel from python SOURCE at runtime
(the role NVRTC plays for CUDA strings) and returns launchable kernels.
`CudaModule` is kept as an informative error — CUDA source cannot target
a TPU.

    mod = mx.rtc.PallasModule(r'''
    def scale_add(x_ref, y_ref, out_ref):
        out_ref[:] = x_ref[:] * 2.0 + y_ref[:]
    ''')
    k = mod.get_kernel("scale_add", num_inputs=2)
    out = k.launch(a, b)          # NDArrays in, NDArray out
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["CudaModule", "PallasModule"]


class CudaModule:
    """NVRTC parity stub: CUDA source has no TPU lowering."""

    def __init__(self, source, options=(), exports=()):
        raise MXNetError(
            "CudaModule compiles CUDA C++ — there is no TPU lowering for "
            "CUDA source. Use mx.rtc.PallasModule with a Pallas kernel "
            "(jax.experimental.pallas) for runtime TPU kernels.")


class PallasKernel:
    """A launchable runtime-compiled kernel."""

    def __init__(self, fn, name, num_inputs, interpret):
        self._fn = fn
        self._name = name
        self._num_inputs = num_inputs
        self._interpret = interpret

    def launch(self, *arrays, out_shape=None, grid=None):
        """Run the kernel over NDArray/jax inputs; returns an NDArray.

        out_shape defaults to the first input's shape/dtype; `grid` is
        forwarded to pallas_call for tiled launches.
        """
        import jax
        import jax.experimental.pallas as pl
        from .ndarray.ndarray import NDArray

        if len(arrays) != self._num_inputs:
            raise MXNetError(
                f"kernel {self._name!r} expects {self._num_inputs} inputs, "
                f"got {len(arrays)}")
        vals = [a._data if isinstance(a, NDArray) else a for a in arrays]
        if out_shape is None:
            out_shape = jax.ShapeDtypeStruct(vals[0].shape, vals[0].dtype)
        # interpret follows the INPUT's device: cpu-resident arrays need
        # the interpreter even when an accelerator backend exists
        interpret = self._interpret
        devs = getattr(vals[0], "devices", None)
        if devs is not None:
            ds = devs()
            if len(ds) == 1:
                interpret = next(iter(ds)).platform == "cpu"
        kwargs = {"out_shape": out_shape, "interpret": interpret}
        if grid is not None:
            kwargs["grid"] = grid
        call = pl.pallas_call(self._fn, **kwargs)
        res = call(*vals)
        # wrap WITHOUT re-committing: array() would copy the result to the
        # default (cpu) context; the kernel output stays on its device
        return res if isinstance(res, NDArray) else NDArray(res)


class PallasModule:
    """Compile Pallas kernels from python source at runtime.

    The source may define any number of kernel functions (signature:
    ``f(*in_refs, out_ref)``); `jnp`, `jax`, `pl`, and `pltpu` are in
    scope. On non-TPU backends kernels run under the Pallas interpreter,
    so the same module works on the CPU test lane.
    """

    def __init__(self, source, exports=()):
        import jax
        import jax.numpy as jnp
        import jax.experimental.pallas as pl
        try:
            import jax.experimental.pallas.tpu as pltpu
        except ImportError:
            pltpu = None
        namespace = {"jax": jax, "jnp": jnp, "pl": pl, "pltpu": pltpu}
        try:
            exec(compile(source, "<rtc source>", "exec"), namespace)
        except Exception as e:
            raise MXNetError(
                f"PallasModule: source failed to compile: {e}") from e
        self._fns = {k: v for k, v in namespace.items()
                     if callable(v) and not k.startswith("_")
                     and k not in ("jax", "jnp", "pl", "pltpu")}
        if exports:
            missing = [e for e in exports if e not in self._fns]
            if missing:
                raise MXNetError(f"PallasModule: exports not found in "
                                 f"source: {missing}")
        # the Pallas interpreter is for the CPU only; a backend that
        # fails to answer is an error, never a reason to interpret
        self._interpret = jax.default_backend() == "cpu"

    def get_kernel(self, name, num_inputs=1, signature=None):
        """Look up a kernel by name. `signature` accepted for CudaModule
        API compatibility (ignored — Pallas refs are typed by launch)."""
        fn = self._fns.get(name)
        if fn is None:
            raise MXNetError(f"no kernel {name!r}; available: "
                             f"{sorted(self._fns)}")
        return PallasKernel(fn, name, num_inputs, self._interpret)
