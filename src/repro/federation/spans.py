"""The host spans of split training, and their names.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs it lands on the host plane of the same trace as the device's
operations, on the same clock; with none running ``span`` returns a
no-op, about a microsecond and a half of host time.  Its keyword stats come back as the event's
stats.  Every span carries ``party``, the party whose thread runs it (an
owner's name, or ``scientist``); the scientist's step spans carry
``step``, the owners' and the wire's ``seq`` (``step = seq //
microbatches``); spans about another party name it as ``peer``.

This module imports no jax: the wire stack runs in jax-free PSI worker
processes, where no profiler can run and ``span`` does nothing.
"""
from __future__ import annotations

import contextlib
import sys

SCIENTIST = "scientist"

#: ``fit(mode="split")`` from entry through owner spawn and the warm-up
FIT_START = "vfl.fit_start"
#: one iteration of the scientist's step loop, bookkeeping included
STEP = "vfl.step"
#: the step's ``head_fwd`` index frames, one to each owner
SEND_FWD = "vfl.send_fwd"
#: the step's labels gathered on the host
LABEL_STAGE = "vfl.label_stage"
#: waiting for one owner's cut (``peer``), its CRC, unpack and decode
CUT_EXCHANGE = "vfl.cut_exchange"
#: one chunk's cuts and labels put on the device in one call (``bytes``)
HOST_STAGE = "vfl.host_stage"
#: dispatch of the trunk's cut-gradient program
TRUNK_CUTGRAD = "vfl.trunk_cutgrad"
#: dispatch of the trunk's weight-gradient program
TRUNK_WEIGHTGRAD = "vfl.trunk_weightgrad"
#: dispatch of the trunk's optimizer update
TRUNK_UPDATE = "vfl.trunk_update"
#: the cut gradients to every owner: defence, codec encode, their fetch
#: with the chunk's metrics where the channels frame them, and send
CUT_GRAD_SEND = "vfl.cut_grad_send"
#: the step's history record, and its metrics' read where no fetch
#: brought them (``backend="direct"``)
BOOKKEEPING = "vfl.bookkeeping"
#: after the last step: the parameter barrier, the owners' stop and join
FIT_END = "vfl.fit_end"
#: an owner's ``head_fwd``: staging, and the forward when it runs at once
OWNER_FWD_REQUEST = "vfl.owner.fwd_request"
#: an owner's ``cut_gradients``: backward, update, next forward and its send
OWNER_CUT_GRAD = "vfl.owner.cut_grad"
#: an owner's codec encode and send of one cut
CUT_ENCODE = "vfl.cut_encode"
#: framing and CRC of one serialized send (``kind``)
WIRE_PACK = "vfl.wire.pack"
#: CRC check and unpack of one serialized receive (``kind``)
WIRE_UNPACK = "vfl.wire.unpack"
#: one device-to-host read of an array, or of a batch of arrays and
#: scalars in one call (``bytes``, the total)
HOST_READ = "vfl.host_read"

SPANS = (FIT_START, STEP, SEND_FWD, LABEL_STAGE, CUT_EXCHANGE, HOST_STAGE,
         TRUNK_CUTGRAD, TRUNK_WEIGHTGRAD, TRUNK_UPDATE, CUT_GRAD_SEND,
         BOOKKEEPING, FIT_END, OWNER_FWD_REQUEST, OWNER_CUT_GRAD, CUT_ENCODE,
         WIRE_PACK, WIRE_UNPACK, HOST_READ)

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **stats):
    """The span ``name`` with ``stats``, as a context manager."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **stats)
