"""ctypes bindings for the native control-plane library.

Builds ``native/libompitpu_native.so`` on demand (g++ is in the image;
pybind11 is not, so the C ABI + ctypes is the binding layer).
"""

from .bindings import (  # noqa: F401
    USER_TAG_BASE, DssBuffer, NativeEventRing, OobEndpoint, ShmRing,
    crc32, load_library, telemetry_symbols_available,
    wire_symbols_available,
)
