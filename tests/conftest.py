"""Test configuration.

Pins JAX to a virtual 8-device CPU mesh BEFORE the backend initializes, so
multi-chip sharding tests (tp/dp/sp over a Mesh) run without TPU hardware.
Mirrors the reference's CI posture of running the full conformance suite on
plain CPU runners (.github/workflows/main.yml).

The pin is ``jax.config.update("jax_platforms", "cpu")`` rather than a
``JAX_PLATFORMS`` default, so the CPU tier means the CPU whatever the
caller's environment says.  Opt in to running the device suites on real
hardware with ``GO_IBFT_TPU_TESTS=1 pytest ...`` (the platform the suite
actually ran on is printed in the header and asserted).

The CPU tier keeps its XLA:CPU artifacts OUTSIDE the checkout: the chip tool
copies the checkout as it stands on disk, and minutes of CPU ladder compiles
are megabytes no chip run can use.  The program's own rule is untouched
(``utils/jaxcache.py``: ``JAX_COMPILATION_CACHE_DIR`` where set, else
``<checkout>/.cache/xla``) — this file merely sets the variable.
"""

import os

_WANT_TPU = os.environ.get("GO_IBFT_TPU_TESTS", "") == "1"
_WANT_PLATFORM = None if _WANT_TPU else "cpu"

# Virtual 8-device CPU mesh: must be in place before the backend initializes.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if not _WANT_TPU:
    # Before jax is imported: jax reads the variable at import.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.expanduser("~/.cache/go_ibft_tpu/xla"),
    )

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

import jax  # noqa: E402

if _WANT_PLATFORM is not None:
    jax.config.update("jax_platforms", _WANT_PLATFORM)

# Persistent XLA compilation cache: the crypto kernels (256-step EC ladders)
# take minutes to compile on CPU the first time; cache makes reruns cheap.
from go_ibft_tpu.utils.jaxcache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()


# Initialize the backend NOW and fail loudly if the platform is not the one
# this suite selected (something initialized jax before the pin above).
_PLATFORM = jax.devices()[0].platform
if _WANT_PLATFORM is not None and _PLATFORM != _WANT_PLATFORM:
    raise RuntimeError(
        f"test platform is {_PLATFORM!r}, wanted {_WANT_PLATFORM!r} — "
        "jax backend initialized before conftest pinned it"
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: large one-time kernel compiles (persistently cached); "
        "deselect with -m 'not slow' for the fast conformance tier",
    )


def pytest_report_header(config):
    return (
        f"jax platform: {_PLATFORM} ({len(jax.devices())} devices)"
        + ("" if _WANT_TPU else " [pinned cpu; GO_IBFT_TPU_TESTS=1 for device runs]")
    )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no plugin dependency).

    After the test body finishes, asserts no asyncio tasks are left running —
    the analogue of the reference's goleak wrapper (core/core_test.go:9-11,
    messages/messages_test.go:59-61).  The check runs *inside* the loop,
    before asyncio.run's implicit cancel-and-close masks leaks.
    """
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }

        async def _run_and_check_leaks():
            await func(**kwargs)
            await asyncio.sleep(0)  # let just-finished tasks settle
            leaked = [
                t
                for t in asyncio.all_tasks()
                if t is not asyncio.current_task() and not t.done()
            ]
            assert not leaked, f"leaked asyncio tasks: {leaked}"

        asyncio.run(_run_and_check_leaks())
        return True
    return None


