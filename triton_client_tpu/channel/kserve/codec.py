"""numpy <-> KServe v2 raw tensor codec, zero-copy where possible.

The reference deserializes ``raw_output_contents`` with a per-scalar
``struct.unpack_from`` python loop (clients/postprocess/
base_postprocess.py:15-37) — O(N) interpreter round-trips per tensor.
Here both directions are single buffer views: ``np.frombuffer`` on
receive (no copy; the protobuf bytes own the memory) and
``ndarray.tobytes()`` / memoryview on send.

Datatype strings follow the KServe v2 table; BF16 travels as uint16
words (the standard Triton convention) and is viewed back at the jax
boundary.
"""

from __future__ import annotations

import ml_dtypes  # ships with jax
import numpy as np

from triton_client_tpu.channel.kserve import pb
from triton_client_tpu.config import config_dtypes
from triton_client_tpu.runtime import faults

# KServe v2 datatype string <-> numpy dtype (little-endian wire order,
# matching the reference's struct '<' formats, base_postprocess.py:20).
# Derived from the single table in config._DTYPES; BF16 is the one
# special case (no stock-numpy dtype) and maps to ml_dtypes.bfloat16.
_BF16 = np.dtype(ml_dtypes.bfloat16)
_TO_NP: dict[str, np.dtype] = {
    k: (_BF16 if v is None else np.dtype(v)) for k, v in config_dtypes().items()
}
_FROM_NP = {v: k for k, v in _TO_NP.items()}

_CONFIG_DTYPE = {
    "BOOL": pb.TYPE_BOOL,
    "UINT8": pb.TYPE_UINT8,
    "UINT16": pb.TYPE_UINT16,
    "UINT32": pb.TYPE_UINT32,
    "UINT64": pb.TYPE_UINT64,
    "INT8": pb.TYPE_INT8,
    "INT16": pb.TYPE_INT16,
    "INT32": pb.TYPE_INT32,
    "INT64": pb.TYPE_INT64,
    "FP16": pb.TYPE_FP16,
    "FP32": pb.TYPE_FP32,
    "FP64": pb.TYPE_FP64,
    "BF16": pb.TYPE_BF16,
}


def datatype_of(arr: np.ndarray) -> str:
    dtype = arr.dtype.newbyteorder("=")
    if dtype not in _FROM_NP:
        raise ValueError(f"unsupported wire dtype {arr.dtype}")
    return _FROM_NP[dtype]


def config_datatype(datatype: str) -> int:
    return _CONFIG_DTYPE.get(datatype, pb.TYPE_INVALID)


def serialize_tensor(arr: np.ndarray) -> bytes:
    """Array -> little-endian raw bytes (C order). A no-copy memoryview
    when the array is already contiguous little-endian."""
    arr = np.ascontiguousarray(arr)
    if arr.dtype.byteorder == ">":
        arr = arr.astype(arr.dtype.newbyteorder("<"))
    return arr.tobytes()


def deserialize_tensor(raw: bytes, datatype: str, shape) -> np.ndarray:
    """Raw bytes -> array view over the buffer (zero copy)."""
    if datatype not in _TO_NP:
        raise ValueError(f"unsupported wire datatype '{datatype}'")
    arr = np.frombuffer(raw, dtype=_TO_NP[datatype])
    return arr.reshape(tuple(int(d) for d in shape))


def set_request_params(msg, params: dict | None) -> None:
    """Write request/response-level ``parameters`` (str -> str/int/bool)
    onto a ModelInfer message: the side-channel trace context
    (``traceparent``), priorities, and span summaries travel here."""
    if not params:
        return
    for key, value in params.items():
        if isinstance(value, bool):
            msg.parameters[key].bool_param = value
        elif isinstance(value, int):
            msg.parameters[key].int64_param = value
        else:
            msg.parameters[key].string_param = str(value)


def get_string_param(msg, key: str) -> str | None:
    """Presence-checked read of a string parameter (bracket access on
    a protobuf map INSERTS a default entry — never subscript blind)."""
    p = msg.parameters
    if key not in p:
        return None
    return p[key].string_param or None


def get_int_param(msg, key: str, default: int = 0) -> int:
    """Presence-checked read of an int64 parameter."""
    p = msg.parameters
    if key not in p:
        return default
    return int(p[key].int64_param)


def get_bool_param(msg, key: str, default: bool = False) -> bool:
    """Presence-checked read of a bool parameter."""
    p = msg.parameters
    if key not in p:
        return default
    return bool(p[key].bool_param)


# streaming-session sequence parameters (runtime/sessions.py): frames
# of one stream share a sequence_id; sequence_start/sequence_end
# bracket the stream's life. Triton's sequence-batcher extension uses
# the same three names, so sequence-aware Triton clients speak this
# without translation.
SEQUENCE_ID_PARAM = "sequence_id"
SEQUENCE_START_PARAM = "sequence_start"
SEQUENCE_END_PARAM = "sequence_end"
PRIORITY_PARAM = "priority"


def sequence_params(msg) -> tuple[str, bool, bool, int]:
    """``(sequence_id, sequence_start, sequence_end, priority)`` of a
    request in ONE access of its parameter map (each ``get_*_param``
    builds the map's wrapper anew; a session's request pays for four).
    Start and end are read only under a ``sequence_id``."""
    p = msg.parameters
    if not p:
        return "", False, False, 0
    priority = int(p[PRIORITY_PARAM].int64_param) if PRIORITY_PARAM in p else 0
    if SEQUENCE_ID_PARAM not in p:
        return "", False, False, priority
    sequence_id = p[SEQUENCE_ID_PARAM].string_param
    if not sequence_id:
        return "", False, False, priority
    return (
        sequence_id,
        SEQUENCE_START_PARAM in p and bool(p[SEQUENCE_START_PARAM].bool_param),
        SEQUENCE_END_PARAM in p and bool(p[SEQUENCE_END_PARAM].bool_param),
        priority,
    )


# multi-frame streaming protocol (round 13): one ModelStreamInfer
# message carries a packed group of G equal-shape frames concatenated
# along the leading axis; the server fans them into the batcher as
# individual requests and streams one response per frame, so a tunnel
# RTT is paid once per group instead of once per frame.
STREAM_GROUP_PARAM = "stream_group"
STREAM_GROUP_IDS_PARAM = "stream_group_ids"


def build_infer_request(
    model_name: str,
    inputs: dict[str, np.ndarray],
    model_version: str = "",
    request_id: str = "",
    parameters: dict | None = None,
    input_parameters: dict[str, dict] | None = None,
) -> pb.ModelInferRequest:
    """``input_parameters`` maps input name -> per-tensor parameters
    (e.g. ``content_encoding`` for wire-compressed payloads,
    runtime/wire_encoding.py)."""
    req = pb.ModelInferRequest(
        model_name=model_name, model_version=model_version, id=request_id
    )
    set_request_params(req, parameters)
    # Sorted for a deterministic input<->raw_input_contents pairing
    # (the wire pairs them by position).
    for name in sorted(inputs):
        arr = np.asarray(inputs[name])
        t = req.inputs.add(
            name=name, datatype=datatype_of(arr), shape=arr.shape
        )
        if input_parameters and name in input_parameters:
            set_request_params(t, input_parameters[name])
        req.raw_input_contents.append(serialize_tensor(arr))
    return req


def build_infer_request_shm(
    model_name: str,
    inputs: dict[str, np.ndarray],
    shm_inputs: dict[str, tuple[str, int, int]],
    model_version: str = "",
    request_id: str = "",
    parameters: dict | None = None,
    input_parameters: dict[str, dict] | None = None,
) -> pb.ModelInferRequest:
    """Like build_infer_request, but inputs named in ``shm_inputs``
    (name -> (region, offset, byte_size)) travel as metadata + shared-
    memory parameters with no raw content; the caller has already
    written their bytes into the region."""
    req = pb.ModelInferRequest(
        model_name=model_name, model_version=model_version, id=request_id
    )
    set_request_params(req, parameters)
    for name in sorted(inputs):
        arr = np.asarray(inputs[name])
        t = req.inputs.add(
            name=name, datatype=datatype_of(arr), shape=arr.shape
        )
        if input_parameters and name in input_parameters:
            set_request_params(t, input_parameters[name])
        target = shm_inputs.get(name)
        if target is None:
            req.raw_input_contents.append(serialize_tensor(arr))
        else:
            set_shm_params(t, *target)
    return req


def add_requested_output(
    req: pb.ModelInferRequest,
    name: str,
    region: str,
    offset: int,
    byte_size: int,
) -> None:
    """Request that the server place one response tensor into a
    client-owned shm window (Triton requested-output semantics): the
    server writes readback bytes straight into the client's mapped
    segment and the response carries only coordinates."""
    t = req.outputs.add(name=name)
    set_shm_params(t, region, offset, byte_size)


def shm_params(tensor) -> tuple[str, int, int] | None:
    """(region, offset, byte_size) when a tensor's parameters request
    shared-memory transport (Triton system-shared-memory extension);
    None for plain wire tensors."""
    p = tensor.parameters
    if "shared_memory_region" not in p:
        return None
    # presence-check before EVERY subscript: bracket access on a
    # protobuf map inserts a default entry, silently mutating the
    # message being parsed — surprising for any later re-serialization
    # or logging of the request/response
    region = p["shared_memory_region"].string_param
    byte_size = (
        int(p["shared_memory_byte_size"].int64_param)
        if "shared_memory_byte_size" in p
        else 0
    )
    offset = (
        int(p["shared_memory_offset"].int64_param)
        if "shared_memory_offset" in p
        else 0
    )
    if not region or byte_size <= 0 or offset < 0:
        raise ValueError(
            "shared-memory tensor parameters need a region name, a "
            "positive byte_size, and a non-negative offset "
            f"(got {region!r}, {byte_size}, {offset})"
        )
    return region, offset, byte_size


def set_shm_params(tensor, region: str, offset: int, byte_size: int) -> None:
    tensor.parameters["shared_memory_region"].string_param = region
    tensor.parameters["shared_memory_byte_size"].int64_param = byte_size
    if offset:
        tensor.parameters["shared_memory_offset"].int64_param = offset


class RequestPlan:
    """What a request's tensor DESCRIPTORS fix, whatever its id, its
    parameters and its payload: how each input is read (name, numpy
    dtype, shape, shm window or the wire), the requested-output
    windows, whether any tensor rides shared memory and how many input
    bytes do. A session's requests repeat their descriptors, so the
    server walks them once (:func:`plan_infer_request`) and looks the
    plan up afterwards under :func:`request_fingerprint`. A pure
    function of the descriptors: a plan is never stale, only unused.
    ``responses`` holds the answer messages built under this plan
    (:func:`build_infer_response`)."""

    __slots__ = (
        "inputs", "wire_inputs", "shm_outputs", "uses_shm", "shm_bytes",
        "responses",
    )


_SERIALIZE_INPUT = pb.ModelInferRequest.InferInputTensor.SerializeToString
_SERIALIZE_OUTPUT = (
    pb.ModelInferRequest.InferRequestedOutputTensor.SerializeToString
)


def request_fingerprint(req: pb.ModelInferRequest) -> tuple:
    """The tensor descriptors' bytes (inputs, requested outputs; no
    payload: that is ``raw_input_contents`` or a shm window's). Equal
    fingerprints are equal descriptors; equal descriptors that
    serialise their parameter maps in another order only miss."""
    return (
        tuple(map(_SERIALIZE_INPUT, req.inputs)),
        tuple(map(_SERIALIZE_OUTPUT, req.outputs)),
    )


def plan_infer_request(req: pb.ModelInferRequest) -> RequestPlan:
    """ONE walk over a request's tensors. Raises ``ValueError`` as
    :func:`parse_infer_request` would for malformed shm parameters or
    an unknown datatype."""
    plan = RequestPlan()
    inputs = []
    plan.shm_bytes = plan.wire_inputs = 0
    for t in req.inputs:
        if t.datatype not in _TO_NP:
            raise ValueError(f"unsupported wire datatype '{t.datatype}'")
        target = shm_params(t)
        if target is None:
            plan.wire_inputs += 1
        else:
            plan.shm_bytes += target[2]
        inputs.append(
            (t.name, _TO_NP[t.datatype], tuple(int(d) for d in t.shape), target)
        )
    plan.inputs = tuple(inputs)
    plan.shm_outputs = {
        t.name: target
        for t in req.outputs
        if (target := shm_params(t)) is not None
    }
    plan.uses_shm = bool(plan.shm_bytes or plan.shm_outputs)
    plan.responses = {}
    return plan


def parse_infer_request(
    req: pb.ModelInferRequest, shm=None, plan: RequestPlan | None = None
) -> dict[str, np.ndarray]:
    """Wire -> arrays. Inputs carrying shared-memory parameters are
    read from ``shm`` (a SystemSharedMemoryRegistry) and consume NO
    raw_input_contents slot — the wire pairs raw buffers positionally
    with the non-shm inputs only (Triton semantics). ``plan``: this
    request's :class:`RequestPlan` where the caller holds it (the walk
    over the descriptors is then not repeated)."""
    faults.probe("codec_decode", req.model_name)
    if plan is None:
        plan = plan_infer_request(req)
    raws = req.raw_input_contents
    if len(raws) != plan.wire_inputs:
        raise ValueError(
            f"{plan.wire_inputs} wire input tensors but "
            f"{len(raws)} raw buffers"
        )
    out = {}
    wire = 0
    for name, dtype, shape, target in plan.inputs:
        if target is None:
            raw = raws[wire]
            wire += 1
        elif shm is None:
            raise ValueError(
                f"input {name!r} requests shared-memory transport but "
                "this server has no shared-memory registry"
            )
        else:
            raw = shm.read(*target)
        out[name] = np.frombuffer(raw, dtype=dtype).reshape(shape)
    return out


_TEMPLATES_A_PLAN = 8


def build_infer_response(
    model_name: str,
    outputs: dict[str, np.ndarray],
    model_version: str = "",
    request_id: str = "",
    shm_outputs: dict[str, tuple[str, int, int]] | None = None,
    shm=None,
    parameters: dict | None = None,
    fallback_to_wire: bool = False,
    templates: dict | None = None,
) -> pb.ModelInferResponse:
    """``shm_outputs`` maps output name -> (region, offset, byte_size):
    those tensors are written into the registry's region and travel as
    metadata + shared-memory parameters with no raw content (Triton
    system-shared-memory extension, response side).

    ``fallback_to_wire``: an output that exceeds its requested window
    ships as raw content instead of raising — the serving path passes
    True so a client whose learned output sizes lag a growing batch
    still gets its response (and learns the larger size from it);
    the strict default stays for direct codec users.

    ``templates``: a dict the caller keeps for ONE set of
    ``shm_outputs`` (a :class:`RequestPlan`'s ``responses``). An answer
    whose model, output names, dtypes and shapes were answered under it
    before is the same message but for its id and payload: the kept
    message is copied and only those are written. At most
    ``_TEMPLATES_A_PLAN`` are kept; further shapes build as before."""
    arrays = [(name, np.asarray(outputs[name])) for name in sorted(outputs)]
    key = None
    if templates is not None and not parameters:
        key = (
            model_name, model_version,
            tuple((name, arr.dtype, arr.shape) for name, arr in arrays),
        )
        kept = templates.get(key)
        if kept is not None:
            message, targets = kept
            resp = pb.ModelInferResponse()
            resp.CopyFrom(message)
            resp.id = request_id
            for (_, arr), target in zip(arrays, targets):
                if target is None:
                    resp.raw_output_contents.append(serialize_tensor(arr))
                else:
                    shm.write(target[0], target[1], arr)
            return resp
    resp = pb.ModelInferResponse(
        model_name=model_name, model_version=model_version, id=request_id
    )
    set_request_params(resp, parameters)
    targets = []  # where each output went: None the wire, else its window
    for name, arr in arrays:
        t = resp.outputs.add(
            name=name, datatype=datatype_of(arr), shape=arr.shape
        )
        target = (shm_outputs or {}).get(name)
        if target is not None and arr.nbytes > target[2]:
            if not fallback_to_wire:
                raise ValueError(
                    f"output {name!r} is {arr.nbytes} bytes but the "
                    f"requested shared-memory window is {target[2]}"
                )
            target = None
        targets.append(target)
        if target is None:
            resp.raw_output_contents.append(serialize_tensor(arr))
            continue
        # single designed copy: readback view -> client's mapped page
        # (write() handles contiguity; no intermediate materialization)
        shm.write(target[0], target[1], arr)
        set_shm_params(t, target[0], target[1], arr.nbytes)
    if key is not None and len(templates) < _TEMPLATES_A_PLAN:
        message = pb.ModelInferResponse()
        message.CopyFrom(resp)
        message.ClearField("id")
        message.ClearField("raw_output_contents")
        templates[key] = (message, tuple(targets))
    return resp


def parse_infer_response(
    resp: pb.ModelInferResponse, regions=None
) -> dict[str, np.ndarray]:
    """Wire -> arrays. Outputs whose parameters carry shared-memory
    coordinates are read from ``regions`` (output name or region name
    -> client-owned SharedMemoryRegion) instead of raw content."""
    wire_outputs = [t for t in resp.outputs if shm_params(t) is None]
    if len(resp.raw_output_contents) != len(wire_outputs):
        raise ValueError(
            f"{len(wire_outputs)} wire output tensors but "
            f"{len(resp.raw_output_contents)} raw buffers"
        )
    raws = iter(resp.raw_output_contents)
    out = {}
    for t in resp.outputs:
        target = shm_params(t)
        if target is None:
            out[t.name] = deserialize_tensor(next(raws), t.datatype, t.shape)
            continue
        name, offset, byte_size = target
        region = (regions or {}).get(name) or (regions or {}).get(t.name)
        if region is None:
            raise ValueError(
                f"response output {t.name!r} lives in shared-memory region "
                f"{name!r} but no matching client region was provided"
            )
        out[t.name] = deserialize_tensor(
            region.read(offset, byte_size), t.datatype, t.shape
        )
    return out
