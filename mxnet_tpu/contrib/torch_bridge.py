"""PyTorch operator bridge — run torch modules inside mxnet graphs.

Parity role: plugin/torch (torch_module.cc `TorchModule`,
torch_criterion.cc `TorchCriterion`, torch_function.cc) — the reference
bridges Lua-Torch nn modules into the operator graph, with the torch
module's weights managed by MXNet as op arguments. Same model here with
modern PyTorch: the wrapped ``torch.nn.Module``'s parameters become
mxnet NDArrays on the tape (gradients flow to them like any other
parameter; train them with an mxnet optimizer), and each application is
a stateless ``torch.func.functional_call`` under an
``mx.autograd.Function`` host callback.

    import torch
    net = torch.nn.Sequential(torch.nn.Linear(8, 4), torch.nn.ReLU())
    op = mx.contrib.torch_bridge.TorchModule(net)
    with mx.autograd.record():
        y = op(x)                    # NDArray out
        loss = ...
    loss.backward()                  # grads land on x AND op.params
    for p in op.params:              # mxnet-side update
        p -= lr * p.grad

Device note: same as mx.operator.CustomOp (host callbacks). Torch itself
runs on its own CPU tensors either way.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError

__all__ = ["TorchModule", "TorchLoss", "eval_function"]


def _torch():
    try:
        import torch
    except ImportError as e:
        raise MXNetError(
            "mx.contrib.torch_bridge requires pytorch "
            "(`pip install torch`)") from e
    return torch


class TorchModule:
    """Wrap a torch.nn.Module as an autograd-aware mxnet op.

    The module's parameters are snapshotted into mxnet NDArrays
    (``.params``, gradients attached); every call applies the module
    STATELESSLY with the current NDArray values, so mxnet optimizers own
    the weights — the reference TorchModule's weights-as-op-arguments
    contract (plugin/torch/torch_module-inl.h).

    Buffers (BatchNorm running stats, ...) are FROZEN snapshots taken at
    wrap time: the functional application passes clones, so in-place
    buffer updates do not persist (and the eager + replay double
    execution cannot double-count them). Wrap modules in eval() mode or
    manage stats torch-side if running statistics matter.
    """

    def __init__(self, module):
        torch = _torch()
        from ..ndarray.ndarray import array
        self._module = module
        self._names = [n for n, _ in module.named_parameters()]
        self.params = []
        for _, p in module.named_parameters():
            nd = array(p.detach().numpy())
            nd.attach_grad()
            self.params.append(nd)
        self._buffers = {n: b.detach().clone()
                         for n, b in module.named_buffers()}

    @property
    def module(self):
        return self._module

    def _functional(self, torch, tins, tparams):
        import torch.func as tf
        pmap = dict(zip(self._names, tparams))
        # clones: keep the stored buffer snapshot immutable (see class doc)
        pmap.update({n: b.clone() for n, b in self._buffers.items()})
        return tf.functional_call(self._module, pmap, tuple(tins))

    def __call__(self, *inputs):
        from .. import autograd
        from .. import random as _mx_random
        torch = _torch()
        bridge = self
        n_in = len(inputs)
        # per-call seed: forward runs twice (eager + backward replay), and
        # stochastic modules (Dropout) must sample the SAME mask both
        # times or gradients decouple from the reported output — mirrors
        # the framework's recorded-rng-key replay discipline
        call_seed = int(_np.asarray(
            _mx_random.next_key()).ravel()[0]) & 0x7FFFFFFF

        class _Fn(autograd.Function):
            def forward(self, *args):
                from ..ndarray.ndarray import array
                # int-dtype inputs (embedding ids) cannot require grad
                tall = []
                for a in args:
                    t = torch.from_numpy(_np.array(a.asnumpy()))
                    if t.is_floating_point() or t.is_complex():
                        t.requires_grad_(True)
                    tall.append(t)
                with torch.random.fork_rng(devices=[]):
                    # CPU generator only: torch.manual_seed would clobber
                    # the user's CUDA/MPS generators, which fork_rng
                    # (devices=[]) does not restore
                    torch.default_generator.manual_seed(call_seed)
                    out = bridge._functional(torch, tall[:n_in],
                                             tall[n_in:])
                self._tall = tall
                self._tout = out
                single = torch.is_tensor(out)
                outs = [out] if single else list(out)
                res = [array(o.detach().numpy()) for o in outs]
                return res[0] if single else tuple(res)

            def backward(self, *ogs):
                from ..ndarray.ndarray import array
                touts = [self._tout] if torch.is_tensor(self._tout) \
                    else list(self._tout)
                gts = [torch.from_numpy(_np.array(g.asnumpy()))
                       for g in ogs]
                diff = [t for t in self._tall if t.requires_grad]
                dgrads = iter(torch.autograd.grad(touts, diff, gts,
                                                  allow_unused=True))
                out = []
                for t in self._tall:
                    g = next(dgrads) if t.requires_grad else None
                    out.append(array(_np.zeros(tuple(t.shape),
                                               _np.float32))
                               if g is None else array(g.numpy()))
                return out[0] if len(out) == 1 else tuple(out)

        return _Fn()(*inputs, *self.params)

    def step(self, lr):
        """Convenience plain-SGD update of the bridged parameters."""
        for p in self.params:
            if p.grad is not None:
                p -= lr * p.grad
                p.grad[:] = 0

    def sync_to_torch(self):
        """Copy the (trained) NDArray values back into the torch module."""
        torch = _torch()
        with torch.no_grad():
            for (_, tp), nd in zip(self._module.named_parameters(),
                                   self.params):
                tp.copy_(torch.from_numpy(_np.array(nd.asnumpy())))


class TorchLoss:
    """Wrap a torch criterion (e.g. ``torch.nn.MSELoss()``) — the role of
    TorchCriterion: (pred, target) in, loss NDArray out; gradients flow
    to pred only (target is detached, as in the reference)."""

    def __init__(self, criterion):
        _torch()
        self._criterion = criterion

    def __call__(self, pred, target):
        from .. import autograd
        torch = _torch()
        criterion = self._criterion

        class _Fn(autograd.Function):
            def forward(self, p, t):
                from ..ndarray.ndarray import array
                tp = torch.from_numpy(_np.array(p.asnumpy())) \
                    .requires_grad_(True)
                tt = torch.from_numpy(_np.array(t.asnumpy()))
                out = criterion(tp, tt)
                self._tp, self._tt, self._out = tp, tt, out
                return array(out.detach().numpy().reshape(
                    tuple(out.shape) if out.dim() else (1,)))

            def backward(self, og):
                from ..ndarray.ndarray import array
                gt = torch.from_numpy(_np.array(og.asnumpy())).reshape(
                    tuple(self._out.shape))
                (gp,) = torch.autograd.grad([self._out], [self._tp], [gt])
                return (array(gp.numpy()),
                        array(_np.zeros(tuple(self._tt.shape),
                                        _np.float32)))

        return _Fn()(pred, target)


def eval_function(fn, *arrays):
    """Apply a non-differentiable torch function to NDArrays eagerly
    (role of torch_function.cc's element-function wrappers)."""
    from ..ndarray.ndarray import array
    torch = _torch()
    tins = [torch.from_numpy(_np.array(a.asnumpy())) for a in arrays]
    out = fn(*tins)
    if torch.is_tensor(out):
        return array(out.numpy())
    return tuple(array(o.numpy()) for o in out)
