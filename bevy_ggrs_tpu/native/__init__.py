"""Native (C++) runtime components, loaded via ctypes.

The session data-plane core (`session_core.cpp`, bound in `core.py` and
`spec.py`) and the batched UDP poller (`udp_poller.cpp`, used by
:mod:`bevy_ggrs_tpu.transport.udp`). Each builds lazily on first use into
a library named after the hash of its source (`build.py`).
"""
