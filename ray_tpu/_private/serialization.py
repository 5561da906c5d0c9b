"""Value serialization for tasks, actors, and objects.

Analog of the reference's SerializationContext
(ray: python/ray/_private/serialization.py:114): cloudpickle for code +
pickle protocol 5 out-of-band buffers so large numpy/jax host arrays are
carried as raw frames (zero-copy into/out of the shared-memory store) rather
than being copied into the pickle stream.

ObjectRefs embedded in values are hooked at (de)serialization time so the
owner can track borrowers, mirroring the reference's reducer hooks for
ObjectRef (ray: python/ray/_private/serialization.py _object_ref_reducer).
"""
from __future__ import annotations

import pickle
import threading
from typing import Any, Callable

import cloudpickle


class SerializedValue:
    """A pickled value plus its out-of-band buffers.

    frames[0] is the pickle stream; frames[1:] are raw PickleBuffer payloads.
    """

    __slots__ = ("frames", "contained_refs")

    def __init__(self, frames: list[bytes], contained_refs: list):
        self.frames = frames
        self.contained_refs = contained_refs

    @property
    def total_bytes(self) -> int:
        return sum(len(f) for f in self.frames)

    def to_payload(self) -> list[bytes]:
        return self.frames


# Thread-local capture of ObjectRefs encountered while pickling a value.
_capture = threading.local()


def _note_ref(ref) -> None:
    lst = getattr(_capture, "refs", None)
    if lst is not None:
        lst.append(ref)


class _Pickler(cloudpickle.CloudPickler):
    def __init__(self, file, buffer_callback):
        super().__init__(file, protocol=5, buffer_callback=buffer_callback)

    def persistent_id(self, obj):  # noqa: D401 - hook, not docstring target
        return None

    def reducer_override(self, obj):
        from ray_tpu.object_ref import ObjectRef

        if isinstance(obj, ObjectRef):
            _note_ref(obj)
            return (ObjectRef._from_serialized, (obj.binary(), obj.owner_addr))
        if obj.__class__ is _ndarray and _unbuffered_dtype(obj):
            return _reduce_unbuffered_ndarray(obj)
        custom = _custom_serializers.get(obj.__class__)
        if custom is not None:
            ser, deser = custom
            # The DESERIALIZER function rides the pickle stream by value
            # (cloudpickle), so receiving workers need no registration
            # (ray: util/serialization.py register_serializer — same
            # one-sided contract).
            return (deser, (ser(obj),))
        return super().reducer_override(obj)


# Exact-type custom reducers (ray: SerializationContext
# _register_cloudpickle_serializer).  Keyed by class; subclasses do NOT
# inherit the serializer (matching the reference).
_custom_serializers: dict = {}


_SAFE_SCALARS = frozenset({type(None), bool, int, float, complex, str,
                           bytes, bytearray})
_SAFE_CONTAINERS = frozenset({list, tuple, set, frozenset})

try:
    import numpy as _np
except Exception:  # noqa: BLE001
    _np = None


_ndarray = _np.ndarray if _np is not None else None


def _unbuffered_dtype(a) -> bool:
    """A dtype numpy registered for somebody else (ml_dtypes' bfloat16,
    the float8s): such an array exports no buffer ("cannot include
    dtype 'E' in a buffer"), so numpy pickles it IN the stream: a
    `tobytes()` copy and the pickler's own, both under the GIL.  A
    34 MB page of bfloat16 KV held the GIL ~100 ms that way and stalled
    the serving engine's thread for as long (PERF.md section 6,
    PR 33)."""
    return a.dtype.isbuiltin == 2


def _ndarray_from_buffer(buf, dtype, shape):
    return _np.frombuffer(buf, dtype=_np.uint8).view(dtype).reshape(shape)


def _reduce_unbuffered_ndarray(a):
    """Out of band like any other array: the bytes ride as a raw frame
    (a view of the array, no copy) and come back as a view of the frame,
    read-only like every array that crosses the object plane."""
    if not a.flags.c_contiguous:
        a = _np.ascontiguousarray(a)
    raw = pickle.PickleBuffer(a.reshape(-1).view(_np.uint8))
    return (_ndarray_from_buffer, (raw, a.dtype, a.shape))


def _stdlib_picklable(v: Any) -> bool:
    """True when the C pickler provably produces the SAME result cloudpickle
    would: exact builtin scalar/container types, object-free numpy arrays,
    and ObjectRefs.  Everything else (instances of user classes — possibly
    defined in __main__, which stdlib pickles by broken reference but
    cloudpickle by value — functions, jax arrays, subclasses) falls back to
    the CloudPickler."""
    t = v.__class__
    if t in _SAFE_SCALARS:
        return True
    if t is dict:
        return all(_stdlib_picklable(k) and _stdlib_picklable(x)
                   for k, x in v.items())
    if t in _SAFE_CONTAINERS:
        return all(_stdlib_picklable(x) for x in v)
    if t is _ndarray:
        # (a registered dtype goes out of band through
        # _Pickler.reducer_override)
        return not v.dtype.hasobject and not _unbuffered_dtype(v)
    from ray_tpu.object_ref import ObjectRef

    return t is ObjectRef


def serialize(value: Any) -> SerializedValue:
    import io

    buffers: list[pickle.PickleBuffer] = []
    _capture.refs = []
    try:
        fast = False
        try:
            fast = _stdlib_picklable(value)
        except RecursionError:
            fast = False
        if fast:
            # Hot path: the C pickler (~10x the pure-Python CloudPickler
            # for small values).  ObjectRef capture still works — its
            # __reduce__ calls _note_ref.
            stream = pickle.dumps(value, protocol=5,
                                  buffer_callback=buffers.append)
        else:
            sink = io.BytesIO()
            _Pickler(sink, buffers.append).dump(value)
            stream = sink.getvalue()
        frames: list = [stream]
        for b in buffers:
            raw = b.raw()   # 1-D C-contiguous "B" view (raises otherwise)
            # Large buffers stay zero-copy views into the source object
            # (numpy/jax host arrays) all the way to the shm arena / wire —
            # the reference's plasma path has the same discipline.  Small
            # ones are snapshotted: cheap, and frees the source immediately.
            frames.append(raw if raw.nbytes >= 1 << 20 else raw.tobytes())
        return SerializedValue(frames, list(_capture.refs))
    finally:
        _capture.refs = None


def _note_deser_ref(ref) -> None:
    """Capture ObjectRefs materialized during a deserialize_with_refs call
    (borrower tracking, ray: serialization.py ObjectRef deserializer hook)."""
    lst = getattr(_capture, "deser_refs", None)
    if lst is not None:
        lst.append(ref)


def deserialize(frames: list[bytes | memoryview]) -> Any:
    bufs = [pickle.PickleBuffer(f) for f in frames[1:]]
    return pickle.loads(frames[0], buffers=bufs)


def deserialize_with_refs(frames: list[bytes | memoryview]) -> tuple[Any, list]:
    """Deserialize and also return the ObjectRefs contained in the value
    (the executing side of the borrow protocol)."""
    bufs = [pickle.PickleBuffer(f) for f in frames[1:]]
    _capture.deser_refs = []
    try:
        value = pickle.loads(frames[0], buffers=bufs)
        return value, list(_capture.deser_refs)
    finally:
        _capture.deser_refs = None


def dumps_function(fn: Callable) -> bytes:
    """Pickle a remote function/actor class for export to the controller KV
    (ray: python/ray/_private/function_manager.py:195 export)."""
    return cloudpickle.dumps(fn)


def loads_function(b: bytes) -> Callable:
    return cloudpickle.loads(b)
