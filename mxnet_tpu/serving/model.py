"""ServedModel / ModelContainer: N models, each pre-compiled at a small
set of padded batch buckets through the unified compile service.

A :class:`ServedModel` wraps one inference function as a pure
``fwd(param_raws, aux_raws, x)`` callable compiled via
:func:`mxnet_tpu.compile.jit` under the ``serving`` site with a
process-stable token — so every bucket executable lands in the
persistent disk cache, records a warmup-manifest entry, and shows up in
``compile.stats()``/churn reports. A warm pod therefore starts with
:func:`mxnet_tpu.compile.warmup` + :meth:`ModelContainer.warmup` and
serves its whole bucket ladder with ZERO recompiles.

Loaders (the same model zoo the C predict ABI speaks):

* :meth:`ServedModel.from_block` — a gluon (Hybrid)Block with
  materialized parameters (the ``capi_bridge``/SymbolBlock surface),
* :meth:`ServedModel.from_symbol` — a Symbol + arg/aux param dicts,
* :meth:`ServedModel.from_checkpoint` — ``prefix-symbol.json`` +
  ``prefix-%04d.params`` (``model.load_checkpoint``),
* :meth:`ServedModel.from_onnx` — a ``.onnx`` file through the existing
  ONNX importer.

Quantized (int8) models load through the SAME loaders: a
``contrib.quantization.quantize_model`` symbol/params pair (or its
``save_checkpoint`` round trip) is detected by its int8 weight params,
reported as ``weight_dtype: "int8"`` in ``stats()``/``/v1/models``, and
compiled under a token salted with the weight dtype — the int8 bucket
ladder gets its own executables in the persistent disk cache, warming
exactly like the float ladder (zero recompiles under traffic after
``warmup()``; docs/PERFORMANCE.md "Int8 inference").

Bucket ladder note: the default smallest bucket is **2**, not 1 — XLA's
CPU matmul takes a GEMV kernel path at batch 1 whose last-bit rounding
differs from the GEMM path every other bucket takes. With buckets >= 2 a
request's response is **bit-identical** no matter which bucket or
batch-mates it was coalesced with (row-independent kernels; padding
never leaks), which the serving test suite asserts.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as _np

from . import config as _config
from .errors import ModelNotFound

__all__ = ["ServedModel", "ModelContainer"]


def _as_raw(v):
    from ..ndarray import NDArray

    if isinstance(v, NDArray):
        return v._data
    import jax.numpy as jnp

    return jnp.asarray(v)


class ServedModel:
    """One inference model: a compiled pure forward + its device-resident
    parameters + a padded-bucket ladder.

    Requests carry an explicit leading batch dim ``(k,) + example_shape``
    (``k >= 1``); the batcher coalesces rows into the nearest bucket.
    """

    def __init__(self, name, forward, param_raws, aux_raws, example_shape,
                 dtype="float32", buckets=None, weight_dtype=None,
                 param_names=None, aux_names=None):
        from .. import compile as _compile

        self.name = str(name)
        self.example_shape = tuple(int(s) for s in example_shape)
        self.dtype = str(dtype)
        # int8-quantized models keep a float INPUT dtype (activations
        # quantize inside the compiled graph) but carry int8 weights;
        # the distinction rides into stats()//models and the compile
        # token so an int8 ladder never collides with its float twin
        if weight_dtype is None:
            weight_dtype = self.dtype
            for r in param_raws:
                if str(getattr(r, "dtype", "")) == "int8":
                    weight_dtype = "int8"
                    break
        self.weight_dtype = str(weight_dtype)
        if buckets is None:
            buckets = _config.effective()["buckets"]
        self.buckets = _config._coerce("buckets", buckets)
        import jax

        # parameters, aux state and every batch are COMMITTED to the one
        # serving device. jit keys its executables on commitment as well
        # as on shape, so a warm-up over host arrays followed by staged
        # (committed) traffic, or by a model-bus swap, would recompile
        # every bucket on its first real batch — behind the back of
        # compile.stats(), which canonicalises both to one signature
        self._device = jax.devices()[0]
        self._praws = tuple(jax.device_put(r, self._device)
                            for r in param_raws)
        self._araws = tuple(jax.device_put(r, self._device)
                            for r in aux_raws)
        # the model-bus census surface: param names (when the loader
        # knows them) + the version/pinned-tuple pair behind live weight
        # swaps. _pinned is rebound as ONE tuple — a batch reads it once,
        # so every request in a batch sees exactly one consistent
        # (params, aux, version) triple however often swap_params runs
        self.param_names = list(param_names) if param_names else None
        self.aux_names = list(aux_names) if aux_names else None
        self._version = 0
        self._swaps = 0
        self._pinned = (self._praws, self._araws, 0)
        # donation of the (freshly staged, never reused) input batch is a
        # memory win on accelerators; CPU jaxlib only warns about it, so
        # gate on platform (the compile service additionally strips
        # donation on cpu under a cache dir — see its platform policy)
        donate = (2,) if self._device.platform != "cpu" else ()
        self._fn = _compile.jit(forward, site="serving",
                                token=self._token(forward),
                                donate_argnums=donate)

    def _token(self, forward):
        base = getattr(forward, "_serving_token", None) or repr(forward)
        blob = "\n".join([str(base), repr(self.example_shape), self.dtype,
                          self.weight_dtype])
        return ("serving", hashlib.sha1(blob.encode()).hexdigest()[:16])

    @property
    def quantized(self):
        """True for an int8-weight (quantized) model."""
        return self.weight_dtype == "int8"

    # ------------------------------------------------------------ shape ---
    @property
    def max_bucket(self):
        return self.buckets[-1]

    def bucket_for(self, rows):
        """Smallest bucket >= rows, or None when rows exceeds the ladder."""
        for b in self.buckets:
            if b >= rows:
                return b
        return None

    def validate(self, arr):
        """Coerce one request payload to ``(k,) + example_shape`` in the
        model dtype; raises ValueError on shape/size mismatch."""
        arr = _np.asarray(arr)
        if arr.shape == self.example_shape:
            arr = arr[None]
        if arr.shape[1:] != self.example_shape:
            raise ValueError(
                f"model {self.name!r} expects rows shaped "
                f"{self.example_shape}, got {arr.shape}")
        if arr.shape[0] < 1:
            raise ValueError(f"model {self.name!r}: empty request")
        if arr.shape[0] > self.max_bucket:
            raise ValueError(
                f"model {self.name!r}: request of {arr.shape[0]} rows "
                f"exceeds the largest bucket {self.max_bucket}; split it "
                "client-side")
        if str(arr.dtype) != self.dtype:
            arr = arr.astype(self.dtype)
        return arr

    # ------------------------------------------------------- live swaps ---
    @property
    def version(self):
        """The model-bus version of the pinned parameters (0 = the
        load-time weights, never swapped)."""
        return self._version

    @property
    def swaps(self):
        """How many times swap_params flipped the pinned weights."""
        return self._swaps

    def pinned(self):
        """The current ``(param_raws, aux_raws, version)`` triple as one
        consistent read (what a batch executes against)."""
        return self._pinned

    def census(self):
        """Per-param ``{name, shape, dtype}`` lists — the shape/dtype
        contract a bus record must match to be applied here."""
        def ents(raws, names):
            return [{"name": names[i] if names else None,
                     "shape": list(r.shape), "dtype": str(r.dtype)}
                    for i, r in enumerate(raws)]
        return {"params": ents(self._praws, self.param_names),
                "aux": ents(self._araws, self.aux_names)}

    def swap_params(self, raws, version, aux_raws=None):
        """Atomically flip the served weights to `raws` (host or device
        arrays in param order), stamping `version`.

        Shapes and dtypes MUST match the live census — that is what
        keeps every compiled bucket executable valid (same avals → the
        in-memory jit cache hits; the swap costs only ``device_put`` of
        the new buffers, ZERO recompiles). The flip itself is one tuple
        rebind: in-flight batches finish on the old weights, the next
        batch runs wholly on the new ones.
        """
        import jax

        cur_p, cur_a, _v = self._pinned

        def staged(news, curs, kind):
            news = tuple(news)
            if len(news) != len(curs):
                raise ValueError(
                    f"model {self.name!r}: swap_params got {len(news)} "
                    f"{kind} arrays, serving {len(curs)}")
            out = []
            for i, (new, cur) in enumerate(zip(news, curs)):
                a = _np.asarray(new) if not hasattr(new, "sharding") \
                    else new
                if tuple(a.shape) != tuple(cur.shape) \
                        or str(a.dtype) != str(cur.dtype):
                    raise ValueError(
                        f"model {self.name!r}: swap_params {kind}[{i}] "
                        f"is {a.shape}/{a.dtype}, serving "
                        f"{cur.shape}/{cur.dtype} — the bus census must "
                        "match (shape-changing updates need a rollout)")
                out.append(jax.device_put(
                    a, getattr(cur, "sharding", None)))
            return tuple(out)

        new_p = staged(raws, cur_p, "param")
        new_a = staged(aux_raws if aux_raws is not None else cur_a,
                       cur_a, "aux")
        self._praws = new_p
        self._araws = new_a
        self._version = int(version)
        self._swaps += 1
        self._pinned = (new_p, new_a, int(version))   # the atomic flip
        return self._pinned

    # -------------------------------------------------------------- run ---
    def run_versioned(self, x, rows=None):
        """:meth:`run`, plus the model version the batch executed under
        — read from the pinned triple ONCE, so the whole batch (and its
        response stamps) is consistent across a concurrent swap."""
        import jax

        praws, araws, version = self._pinned
        out = self._fn(praws, araws, jax.device_put(x, self._device))
        outs = out if isinstance(out, tuple) else (out,)
        host = jax.device_get(outs)
        n = x.shape[0] if rows is None else rows
        return [_np.asarray(o)[:n] for o in host], version

    def run(self, x, rows=None):
        """Execute the compiled forward on a (padded) batch and return the
        outputs as host numpy arrays, sliced to ``rows``. BLOCKS on the
        device→host copy — the batcher always calls this inside a
        ``watchdog.sync('serving.batch', ...)`` span, so a wedged batch
        surfaces as a StallError + crash bundle, never a hung server."""
        return self.run_versioned(x, rows)[0]

    def warmup(self):
        """Compile (or disk-load) every bucket executable ahead of
        traffic; returns a small report. Combined with
        ``compile.warmup()`` this is the warm-pod start: zero recompiles
        once traffic arrives."""
        import time

        t0 = time.perf_counter()
        for b in self.buckets:
            x = _np.zeros((b,) + self.example_shape, dtype=self.dtype)
            self.run(x, 0)
        return {"buckets": list(self.buckets),
                "ms": round((time.perf_counter() - t0) * 1e3, 1)}

    def __repr__(self):
        return (f"ServedModel({self.name!r}, example={self.example_shape}, "
                f"dtype={self.dtype}, weight_dtype={self.weight_dtype}, "
                f"buckets={self.buckets})")

    # ---------------------------------------------------------- loaders ---
    @classmethod
    def from_block(cls, name, block, example_shape, dtype="float32",
                   buckets=None):
        """Serve a gluon (Hybrid)Block with materialized parameters.
        Parameters are snapshotted at build time (later training does not
        leak into serving)."""
        from .. import autograd
        from ..cached_op import TraceScope
        from ..ndarray import NDArray

        params = block.collect_params()
        handles = []
        for pname, p in params.items():
            if p._data is None:
                raise ValueError(
                    f"model {name!r}: parameter {pname!r} not initialized; "
                    "run one forward pass (or initialize with explicit "
                    "shapes) first")
            handles.append(p.data())

        def fwd(praws, araws, x):
            # the ShardedTrainer.predict idiom: rebind the live handles to
            # the traced values for the duration of the trace
            import jax

            saved = [(h, h._data) for h in handles]
            try:
                for h, r in zip(handles, praws):
                    h._data = r
                # the scope inlines hybridized children into THIS trace
                # (their CachedOps would otherwise draw from the global
                # RNG mid-trace); fixed key: inference is deterministic
                with TraceScope(jax.random.PRNGKey(0)), \
                        autograd.pause(train_mode=False):
                    out = block.forward(NDArray(x))
                outs = out if isinstance(out, (tuple, list)) else (out,)
                return tuple(o._data for o in outs)
            finally:
                for h, orig in saved:
                    h._data = orig

        fwd._serving_token = ("block", repr(block), tuple(params))
        # a REAL snapshot, not an alias: a ShardedTrainer over the same
        # block donates its param buffers every step, which would tear
        # the served weights out from under in-flight batches in a
        # train-and-serve process (the model-bus topology).  Round-trip
        # through host so the snapshot also sheds any mesh sharding the
        # trainer put on the source buffers — serving inputs live on the
        # default device, and a committed multi-device parameter would
        # make the jitted forward reject the batch.
        import jax
        import jax.numpy as jnp

        praws = tuple(jnp.asarray(_np.asarray(jax.device_get(h._data)))
                      for h in handles)
        return cls(name, fwd, praws, (), example_shape, dtype, buckets,
                   param_names=list(params))

    @classmethod
    def from_symbol(cls, name, sym, arg_params=None, aux_params=None,
                    input_name=None, example_shape=None, dtype="float32",
                    buckets=None):
        """Serve a Symbol graph + parameter dicts (the MXPred surface)."""
        if example_shape is None:
            raise ValueError("from_symbol requires example_shape (the "
                             "per-row input shape, without the batch dim)")
        arg_params = dict(arg_params or {})
        aux_params = dict(aux_params or {})
        arg_names = list(sym.list_arguments())
        aux_names = list(sym.list_auxiliary_states())
        if input_name is None:
            data_names = [n for n in arg_names if n not in arg_params]
            if len(data_names) != 1:
                raise ValueError(
                    f"model {name!r}: cannot infer the data input from "
                    f"{data_names or arg_names}; pass input_name=")
            input_name = data_names[0]
        elif input_name not in arg_names:
            raise ValueError(f"model {name!r}: {input_name!r} is not an "
                             f"argument of the symbol ({arg_names})")
        pnames = [n for n in arg_names if n != input_name]
        missing = [n for n in pnames if n not in arg_params] + \
                  [n for n in aux_names if n not in aux_params]
        if missing:
            raise ValueError(
                f"model {name!r}: no parameter values for {missing}")
        run = sym._build_eval()

        def fwd(praws, araws, x):
            import jax

            args = dict(zip(pnames, praws))
            args[input_name] = x
            auxs = dict(zip(aux_names, araws))
            # fixed key: inference is deterministic (dropout is identity
            # with training=False; the key is only plumbing)
            outs, _ = run(args, auxs, jax.random.PRNGKey(0), False)
            return tuple(outs)

        fwd._serving_token = ("symbol",
                              hashlib.sha1(
                                  sym.tojson().encode()).hexdigest()[:16],
                              input_name, tuple(pnames))
        praws = tuple(_as_raw(arg_params[n]) for n in pnames)
        araws = tuple(_as_raw(aux_params[n]) for n in aux_names)
        return cls(name, fwd, praws, araws, example_shape, dtype, buckets,
                   param_names=pnames, aux_names=aux_names)

    @classmethod
    def from_checkpoint(cls, name, prefix, epoch, example_shape,
                        dtype="float32", buckets=None, input_name=None):
        """Serve a ``save_checkpoint`` pair (symbol json + params)."""
        from ..model import load_checkpoint

        sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls.from_symbol(name, sym, arg_params, aux_params,
                               input_name=input_name,
                               example_shape=example_shape, dtype=dtype,
                               buckets=buckets)

    @classmethod
    def from_onnx(cls, name, model_file, example_shape, dtype="float32",
                  buckets=None, input_name=None):
        """Serve a ``.onnx`` file through the existing ONNX importer."""
        from ..onnx.onnx2mx import import_model

        sym, arg_params, aux_params = import_model(model_file)
        return cls.from_symbol(name, sym, arg_params, aux_params,
                               input_name=input_name,
                               example_shape=example_shape, dtype=dtype,
                               buckets=buckets)


class ModelContainer:
    """An ordered, named set of :class:`ServedModel`\\ s — what a
    :class:`~mxnet_tpu.serving.server.ModelServer` serves."""

    def __init__(self, models=None):
        self._models = OrderedDict()
        for m in models or ():
            self.add(m)

    def add(self, model: ServedModel) -> ServedModel:
        if model.name in self._models:
            raise ValueError(f"model {model.name!r} already in container")
        self._models[model.name] = model
        return model

    # convenience constructors mirroring the ServedModel loaders
    def add_block(self, name, block, example_shape, **kw):
        return self.add(ServedModel.from_block(name, block, example_shape,
                                               **kw))

    def add_symbol(self, name, sym, arg_params=None, aux_params=None, **kw):
        return self.add(ServedModel.from_symbol(name, sym, arg_params,
                                                aux_params, **kw))

    def add_checkpoint(self, name, prefix, epoch, example_shape, **kw):
        return self.add(ServedModel.from_checkpoint(name, prefix, epoch,
                                                    example_shape, **kw))

    def add_onnx(self, name, model_file, example_shape, **kw):
        return self.add(ServedModel.from_onnx(name, model_file,
                                              example_shape, **kw))

    def names(self):
        return list(self._models)

    def get(self, name) -> ServedModel:
        m = self._models.get(name)
        if m is None:
            raise ModelNotFound(
                f"model {name!r} not in container; available: "
                f"{sorted(self._models)}")
        return m

    def __getitem__(self, name):
        return self.get(name)

    def __contains__(self, name):
        return name in self._models

    def __iter__(self):
        return iter(self._models.values())

    def __len__(self):
        return len(self._models)

    def warmup(self):
        """Warm-pod start: replay the compile service's warmup manifest
        (disk-cache loads for every previously-seen signature), then walk
        every model's bucket ladder. After this, steady-state traffic
        shows only cache hits in ``compile.stats()``."""
        from .. import compile as _compile

        report = {"service": _compile.warmup(), "models": {}}
        for m in self:
            report["models"][m.name] = m.warmup()
        return report
