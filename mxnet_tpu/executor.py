"""Executor: compiled evaluation of a Symbol graph.

Reference: src/executor/graph_executor.cc — GraphExecutor::Init runs nnvm
passes (shape/type infer, PlanMemory, AttachOpExecs, InitCachedOps) then
Forward/Backward replay cached engine ops (:64-93, :1318).

TPU-native: "Init" = trace the DAG into one JAX function; jit compiles the
whole graph as a single XLA module (forward) and jax.vjp provides backward —
XLA's buffer assignment replaces PlanMemory, fusion replaces op bulking, and
donation replaces the shared-memory-pool trick (graph_executor.cc:927).
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray, _wrap, zeros as nd_zeros
from .ops.registry import get_op
from . import autograd


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        from .context import current_context
        self._symbol = symbol
        self._ctx = ctx or current_context()
        # manual model parallelism (reference graph_executor.cc:908
        # AssignContext): ops whose ctx_group attr maps to a Context run on
        # that device, with transfers at group boundaries (the
        # _CrossDeviceCopy analog is jax.device_put between groups)
        self._group2ctx = dict(group2ctx) if group2ctx else None
        self._group2dev = {name: c.jax_device()
                           for name, c in (group2ctx or {}).items()}
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            args = dict(zip(self.arg_names, args))
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(self.aux_names, aux_states))
        self.arg_dict = dict(args)
        self.aux_dict = dict(aux_states or {})
        self._aux_update_names = []  # set by _build_fn(is_train=True)
        self._aux_tail = ()
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(self.arg_names, args_grad))
        self.grad_dict = dict(args_grad) if args_grad else {}
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = dict(grad_req)
        self.outputs = []
        self._fwd_train = None
        self._fwd_infer = None
        self._vjp = None
        self._jit_train_fwd = None
        self._jit_train_bwd = None
        self._jit_wrt = None       # wrt snapshot the jitted pair was built for
        self._monitor_callback = None

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    # ------------------------------------------------------------------
    # ops whose (out, mean, var) training outputs fold into the moving-stat
    # aux inputs [3]=moving_mean, [4]=moving_var (batch_norm.cc:118-140:
    # moving = moving * momentum + batch * (1 - momentum))
    _BN_AUX_OPS = frozenset(("BatchNorm", "_contrib_SyncBatchNorm"))

    def _build_fn(self, is_train):
        """Trace the DAG into fn(arg_vals_list, aux_vals_list, keys) ->
        outs + updated-aux tail.

        The reference's BatchNorm MUTATES its moving_mean/moving_var aux
        states during every training forward; this pure trace instead
        APPENDS each touched aux's updated value after the graph outputs,
        and forward() writes the tail back into aux_dict — without this,
        Module-trained BN nets kept their init (0, 1) running stats and
        normalized garbage at inference (round-5 audit find)."""
        sym = self._symbol
        nodes = sym._topo_nodes()
        arg_order = {n: i for i, n in enumerate(self.arg_names)}
        aux_order = {n: i for i, n in enumerate(self.aux_names)}
        rng_nodes = [n for n in nodes
                     if n.op is not None and get_op(n.op).rng_for(n.attrs)]
        rng_index = {id(n): i for i, n in enumerate(rng_nodes)}

        bn_nodes = []
        if is_train:
            aux_update_names = []
            for n in nodes:
                if (n.op in self._BN_AUX_OPS and len(n.inputs) >= 5
                        and not n.attrs.get("use_global_stats", False)):
                    mm, mv = n.inputs[3][0], n.inputs[4][0]
                    if mm.name in aux_order and mv.name in aux_order:
                        bn_nodes.append(n)
                        aux_update_names += [mm.name, mv.name]
            # train-only state: the infer build must not clobber it (the
            # two traced fns are cached independently per mode)
            self._aux_update_names = aux_update_names

        group2dev = self._group2dev
        default_dev = self._ctx.jax_device() if group2dev else None

        def fn(arg_vals, aux_vals, keys):
            import jax
            env = {}
            for n in nodes:
                if n.op is None:
                    if n.attrs.get("__is_aux__"):
                        env[(id(n), 0)] = aux_vals[aux_order[n.name]]
                    else:
                        env[(id(n), 0)] = arg_vals[arg_order[n.name]]
                    continue
                op = get_op(n.op)
                attrs = {k: v for k, v in n.attrs.items()
                         if not k.startswith("__") and k != "ctx_group"}
                if op.mode_for(attrs):
                    attrs["_training"] = is_train
                if op.rng_for(attrs):
                    attrs["_rng_key"] = keys[rng_index[id(n)]]
                in_vals = [env[(id(inp), idx)] for (inp, idx) in n.inputs]
                if group2dev:
                    # cross-device copy onto this op's assigned device;
                    # ungrouped ops run on the bind context (AssignContext
                    # default-context behavior)
                    dev = group2dev.get(n.attrs.get("ctx_group"), default_dev)
                    in_vals = [jax.device_put(v, dev) for v in in_vals]
                # the node's name in every device operation's op_name
                # (trace time only): .../fwd/stage1_conv0/conv_general_dilated
                with jax.named_scope(n.name):
                    out = op.fcompute(attrs, *in_vals)
                outs = out if isinstance(out, (tuple, list)) else [out]
                for i, o in enumerate(outs):
                    env[(id(n), i)] = o
            result = [env[(id(n), idx)] for (n, idx) in sym._entries]
            from .ops.nn_ops import BN_EPS_DEFAULT, bn_invstd_to_var
            for n in bn_nodes:
                m = float(n.attrs.get("momentum", 0.9))
                eps = float(n.attrs.get("eps", BN_EPS_DEFAULT))
                mean, invstd = env[(id(n), 1)], env[(id(n), 2)]
                # the op's third output is invstd (reference contract);
                # the running average tracks the raw variance
                var = bn_invstd_to_var(invstd, eps)
                old_mm = env[(id(n.inputs[3][0]), n.inputs[3][1])]
                old_mv = env[(id(n.inputs[4][0]), n.inputs[4][1])]
                result.append(old_mm * m + mean * (1 - m))
                result.append(old_mv * m + var * (1 - m))
            return result

        self._n_rng = len(rng_nodes)
        return fn

    def _keys(self):
        import jax
        from . import random as _random
        if self._n_rng == 0:
            import jax.numpy as jnp
            return jnp.zeros((1, 2), dtype=jnp.uint32)
        return jax.numpy.stack([_random.next_key() for _ in range(self._n_rng)])

    def forward(self, is_train=False, **kwargs):
        import jax
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(v._data if isinstance(v, NDArray)
                                           else jax.numpy.asarray(v))
        arg_vals = [self.arg_dict[n]._data for n in self.arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self.aux_names]
        if is_train:
            if self._fwd_train is None:
                self._raw_train = self._fwd_train = self._build_fn(True)
            keys = self._keys()
            wrt_names = [n for n in self.arg_names
                         if self.grad_req.get(n, "null") != "null"]
            wrt_idx = [self.arg_names.index(n) for n in wrt_names]
            if self._group2dev:
                # per-op device placement needs eager dispatch, so the vjp
                # is built at forward time (re-traced per call — group2ctx
                # is a placement feature, not a throughput path)
                def f_wrt(*wrt_vals):
                    vals = list(arg_vals)
                    for i, v in zip(wrt_idx, wrt_vals):
                        vals[i] = v
                    return tuple(self._raw_train(vals, aux_vals, keys))

                outs, vjp = jax.vjp(f_wrt, *[arg_vals[i] for i in wrt_idx])
                self._vjp = (vjp, wrt_names)
            else:
                # compiled train path: jitted forward + separately-jitted
                # recompute backward, both cached on the executor — per-step
                # jax.vjp would re-trace the whole graph every iteration
                # (same defect class as CachedOp._get_bwd; see cached_op.py)
                if (self._jit_train_fwd is None
                        or self._jit_wrt != tuple(wrt_idx)):
                    raw = self._raw_train
                    idx = tuple(wrt_idx)
                    self._jit_train_fwd = jax.jit(
                        lambda a, x, k: tuple(raw(list(a), x, k)))

                    def bwd(a, x, k, cts):
                        def f_wrt(*wv):
                            vals = list(a)
                            for i, v in zip(idx, wv):
                                vals[i] = v
                            return tuple(raw(vals, x, k))
                        wv = [a[i] for i in idx]
                        return jax.vjp(f_wrt, *wv)[1](cts)
                    self._jit_train_bwd = jax.jit(bwd)
                    self._jit_wrt = idx
                outs = self._jit_train_fwd(tuple(arg_vals), tuple(aux_vals),
                                           keys)
                saved = (tuple(arg_vals), tuple(aux_vals), keys)
                bwd_fn = self._jit_train_bwd
                self._vjp = ((lambda cts: bwd_fn(*saved, cts)), wrt_names)
            # split off the appended BN moving-stat updates and fold them
            # into aux_dict (the pure-trace analog of the reference op's
            # in-place running-stat mutation)
            n_graph = len(outs) - len(self._aux_update_names)
            self._aux_tail = tuple(outs[n_graph:])
            for name, val in zip(self._aux_update_names, outs[n_graph:]):
                self.aux_dict[name]._set_data(val)
            outs = outs[:n_graph]
            self.outputs = [_wrap(o, ctx=self._ctx) for o in outs]
        else:
            if self._fwd_infer is None:
                raw = self._build_fn(False)
                # group2ctx placement needs eager dispatch: inside one jit,
                # XLA owns placement and per-op device pins are not honored
                self._fwd_infer = raw if self._group2dev else \
                    jax.jit(lambda a, x, k: tuple(raw(a, x, k)))
                self._raw_infer = raw
            keys = self._keys()
            outs = self._fwd_infer(arg_vals, aux_vals, keys)
            self.outputs = [_wrap(o, ctx=self._ctx) for o in outs]
        if self._monitor_callback is not None:
            for name, out in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, out)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        import jax.numpy as jnp
        if self._vjp is None:
            raise MXNetError("must call forward(is_train=True) before backward")
        vjp, wrt_names = self._vjp
        if out_grads is None:
            cts = tuple(jnp.ones_like(o._data) for o in self.outputs)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cts = tuple(g._data for g in out_grads)
        if self._group2dev:
            # head gradients must live where their outputs were produced —
            # the reverse pass then threads device_put transposes backwards
            import jax
            cts = tuple(jax.device_put(g, list(o._data.devices())[0])
                        for g, o in zip(cts, self.outputs))
        # the traced function also returned BN moving-stat updates; their
        # cotangents are zero (running stats are autograd.pause state)
        if getattr(self, "_aux_tail", ()):
            cts = cts + tuple(jnp.zeros_like(t) for t in self._aux_tail)
        grads = vjp(cts)
        for name, g in zip(wrt_names, grads):
            req = self.grad_req.get(name, "write")
            if req == "null":
                continue
            if name not in self.grad_dict or self.grad_dict[name] is None:
                self.grad_dict[name] = _wrap(g, ctx=self._ctx)
            elif req == "add":
                self.grad_dict[name]._set_data(self.grad_dict[name]._data + g)
            else:
                self.grad_dict[name]._set_data(g)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor for new input shapes (XLA recompiles per
        shape; the jit cache keeps previously-seen shapes hot — the analog of
        GraphExecutor::Reshape, graph_executor.cc:786)."""
        var_groups = self._symbol._variable_groups() if self._group2ctx else {}

        def alloc_ctx(name):
            group = var_groups.get(name)
            if self._group2ctx and group in self._group2ctx:
                return self._group2ctx[group]
            return self._ctx

        new_args = {}
        for n in self.arg_names:
            if n in kwargs:
                new_args[n] = nd_zeros(kwargs[n], ctx=alloc_ctx(n))
            else:
                new_args[n] = self.arg_dict[n]
        new_grads = None
        if self.grad_dict:
            new_grads = {n: nd_zeros(new_args[n].shape, ctx=alloc_ctx(n))
                         for n in self.grad_dict if self.grad_dict[n] is not None}
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self.grad_req, dict(self.aux_dict),
                        group2ctx=self._group2ctx)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise ValueError("Find name \"%s\" that is not in the auxiliary "
                                     "states" % name)

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback

    def debug_str(self):
        return "Executor(symbol=%s, args=%s)" % (self._symbol.name, self.arg_names)
