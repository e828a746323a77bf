"""Run-metadata stamp shared by every artifact writer.

VERDICT weak #5: experiment artifacts shipped without platform /
jax-version metadata, so a published number could not be tied to the
hardware that produced it (MLPerf-style run stamping — PAPERS.md).
``run_metadata()`` is the one shared helper; the fast-tier contract test
(tests/test_obs.py) fails any ``experiments/`` artifact writer that does
not reference it.

Device discovery is cached per process (``jax.devices()`` initialises
the backend).  A backend that cannot start raises here as it would
anywhere: a stamp never names a platform that was not there.
"""

from __future__ import annotations

import functools
import platform as _platform
import sys
import time
from typing import Any, Dict

RUN_METADATA_SCHEMA = "tddl-obs-v1"

#: Keys every stamped artifact must carry (the contract test checks the
#: helper is used; unit tests check the helper emits these).
RUN_METADATA_KEYS = (
    "schema", "platform", "device_kind", "num_devices", "jax_version",
    "python_version", "framework_version", "hostname", "timestamp",
)


@functools.lru_cache(maxsize=1)
def _device_info() -> Dict[str, Any]:
    """Backend identity, resolved once per process."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "num_devices": len(devices),
        "jax_version": jax.__version__,
    }


def run_metadata(host_only: bool = False) -> Dict[str, Any]:
    """The metadata block every published JSON artifact embeds.

    ``host_only=True`` skips device discovery entirely (platform
    ``"unprobed"``) — for the host-only modules (the verdict store, the
    incident assembler), which must never import JAX."""
    from trustworthy_dl_tpu import __version__

    meta = {
        "schema": RUN_METADATA_SCHEMA,
        "python_version": sys.version.split()[0],
        "framework_version": __version__,
        "hostname": _platform.node(),
        "timestamp": time.time(),
    }
    if host_only:
        try:
            import importlib.metadata as _md

            jax_version = _md.version("jax")
        except Exception:
            jax_version = "unknown"
        meta.update({
            "platform": "unprobed",
            "device_kind": "unknown",
            "num_devices": 0,
            "jax_version": jax_version,
        })
        return meta
    meta.update(_device_info())
    return meta
