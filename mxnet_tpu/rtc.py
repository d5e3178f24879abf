"""Runtime-compiled custom kernels (ref: python/mxnet/rtc.py CudaModule
over NVRTC, include/mxnet/rtc.h:39, src/common/rtc.cc:49,86).

TPU-native redesign: the NVRTC "compile CUDA C at runtime" story becomes
"compile a Pallas kernel at runtime". ``PallasModule`` takes Python source
text defining Pallas kernel functions (ref-style: ``def k(x_ref, o_ref)``),
compiles them through ``pl.pallas_call`` on first launch, and caches per
(shapes, dtypes, grid) — the same lifecycle as CudaModule.get_kernel +
CudaKernel.launch. On non-TPU backends kernels run in Pallas interpret
mode so the code path is testable anywhere.

``CudaModule`` is kept as an API-compat shim that raises with a pointer
here.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as _np

from .base import MXNetError, check

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]


class PallasKernel:
    """A launchable kernel (ref: rtc.py CudaKernel).

    ``launch(args, grid=...)`` maps to the reference's
    ``kernel.launch(args, ctx, grid_dims, block_dims)``: the CUDA
    grid/block pair collapses into the Pallas grid (blocking is expressed
    by in_specs/out_specs when given).
    """

    def __init__(self, name: str, fn, out_shape, out_dtype,
                 grid: Optional[Tuple[int, ...]], in_specs, out_specs):
        self._name = name
        self._fn = fn
        self._out_shape = out_shape
        self._out_dtype = out_dtype
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs
        self._cache: Dict = {}

    def _compiled(self, in_shapes, in_dtypes, grid, interpret):
        key = (in_shapes, in_dtypes, grid, interpret)
        cached = self._cache.get(key)
        if cached is not None:
            return cached
        import jax
        from jax.experimental import pallas as pl

        multi = isinstance(self._out_shape, (list, tuple)) and \
            self._out_shape and isinstance(self._out_shape[0],
                                           (list, tuple))
        if multi:
            dts = self._out_dtype if isinstance(self._out_dtype,
                                                (list, tuple)) \
                else [self._out_dtype] * len(self._out_shape)
            out_sds = [jax.ShapeDtypeStruct(tuple(s), _np.dtype(d))
                       for s, d in zip(self._out_shape, dts)]
        else:
            out_sds = jax.ShapeDtypeStruct(tuple(self._out_shape),
                                           _np.dtype(self._out_dtype))
        kwargs = {}
        if grid:
            kwargs["grid"] = grid
        if self._in_specs is not None:
            kwargs["in_specs"] = self._in_specs
        if self._out_specs is not None:
            kwargs["out_specs"] = self._out_specs
        call = pl.pallas_call(self._fn, out_shape=out_sds,
                              interpret=interpret, **kwargs)
        jitted = jax.jit(call)
        self._cache[key] = jitted
        return jitted

    def launch(self, args: Sequence, ctx=None, grid_dims=None,
               block_dims=None, shared_mem: int = 0):
        """Run the kernel. args: NDArrays (or jax arrays); returns
        NDArray(s). ctx/block_dims/shared_mem accepted for API compat
        with CudaKernel.launch; blocking is expressed via specs/grid."""
        from .ndarray.ndarray import NDArray, from_jax
        if isinstance(args, NDArray) or not isinstance(args,
                                                       (list, tuple)):
            args = [args]
        arrs = [a._data if isinstance(a, NDArray) else a for a in args]
        from .ops.pallas_kernels import _interpret_for
        grid = tuple(grid_dims) if grid_dims else (self._grid or ())
        jitted = self._compiled(tuple(a.shape for a in arrs),
                                tuple(str(a.dtype) for a in arrs),
                                tuple(grid),
                                _interpret_for(arrs[0] if arrs else None))
        out = jitted(*arrs)
        if isinstance(out, (list, tuple)):
            return [from_jax(o) for o in out]
        return from_jax(out)

    __call__ = launch

    def __repr__(self):
        return f"<PallasKernel {self._name}>"


class PallasModule:
    """Compile Pallas kernel source at runtime (ref: rtc.py CudaModule).

    ``source`` is Python text; every top-level function it defines is an
    exportable kernel written against the Pallas ref model
    (``def scale(x_ref, o_ref): o_ref[...] = x_ref[...] * 2``). The
    namespace is pre-seeded with jnp / jax / pl (and pltpu on TPU builds),
    mirroring how CudaModule sources assume the CUDA toolchain headers.
    """

    def __init__(self, source: str, options: Sequence[str] = (),
                 exports: Sequence[str] = ()):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        ns = {"jax": jax, "jnp": jnp, "pl": pl, "np": _np}
        try:
            from jax.experimental.pallas import tpu as pltpu
            ns["pltpu"] = pltpu
        except ImportError:  # pragma: no cover
            pass
        try:
            exec(compile(source, "<rtc.PallasModule>", "exec"), ns)
        except SyntaxError as e:
            raise MXNetError(f"PallasModule source failed to parse: {e}")
        self._fns = {
            k: v for k, v in ns.items()
            if getattr(v, "__code__", None) is not None
            and v.__code__.co_filename == "<rtc.PallasModule>"}
        exports = tuple(exports)
        if exports:
            missing = [e for e in exports if e not in self._fns]
            check(not missing,
                  f"exports {missing} not defined in PallasModule source")
            self._fns = {k: self._fns[k] for k in exports}
        check(bool(self._fns),
              "PallasModule source defines no kernel functions")

    def get_kernel(self, name: str, out_shape=None, out_dtype="float32",
                   grid: Optional[Tuple[int, ...]] = None,
                   in_specs=None, out_specs=None,
                   signature: Optional[str] = None) -> PallasKernel:
        """Fetch a kernel by name (ref: CudaModule.get_kernel(name,
        signature)). The CUDA type-signature string is replaced by
        out_shape/out_dtype (+ optional grid and block specs)."""
        check(name in self._fns,
              f"kernel {name!r} not found; module defines "
              f"{sorted(self._fns)}")
        check(out_shape is not None,
              "get_kernel requires out_shape (the XLA analog of the "
              "CUDA signature string)")
        return PallasKernel(name, self._fns[name], out_shape, out_dtype,
                            grid, in_specs, out_specs)


class CudaModule:
    """API-compat shim for the reference's NVRTC module."""

    def __init__(self, *a, **kw):
        raise MXNetError(
            "CUDA RTC is not available in the TPU build; write runtime "
            "kernels with mxnet_tpu.rtc.PallasModule instead "
            "(ref: python/mxnet/rtc.py)")
