"""Environment diagnostic (reference tools/diagnose.py: platform, package
versions, and health checks — minus its network reachability tests, which
a zero-egress build cannot run).

Prints python/OS/CPU info, the versions of every runtime dependency, the
honored MXNET_* environment knobs (mxnet_tpu.env registry), the compile
cache directory, the native library build states, and the devices JAX sees
from this process (which claims the chip while it runs).

Usage: python tools/diagnose.py
"""
import argparse
import os
import platform
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def section(title):
    print("\n----- %s -----" % title)


def main():
    argparse.ArgumentParser(description=__doc__).parse_args()

    section("Platform")
    print("python   :", sys.version.replace("\n", " "))
    print("platform :", platform.platform())
    print("machine  :", platform.machine())
    try:
        print("cpus     :", os.cpu_count())
    except Exception:
        pass

    section("Package versions")
    from importlib import metadata
    for dist in ("numpy", "jax", "jaxlib", "libtpu", "flax", "optax",
                 "orbax-checkpoint"):
        try:
            print("%-18s %s" % (dist, metadata.version(dist)))
        except metadata.PackageNotFoundError:
            print("%-18s not installed" % dist)
    try:
        import mxnet_tpu
        print("%-18s %s" % ("mxnet_tpu", mxnet_tpu.__version__))
    except Exception as e:
        # a broken install is exactly when diagnostics matter: keep going
        print("%-18s IMPORT FAILED (%s: %s)"
              % ("mxnet_tpu", type(e).__name__, e))

    section("Environment knobs (mxnet_tpu.env registry)")
    try:
        from mxnet_tpu import env
        set_knobs = [(k, os.environ[k]) for k in sorted(env.VARIABLES)
                     if k in os.environ]
        if set_knobs:
            for k, v in set_knobs:
                print("%-40s = %s" % (k, v))
        else:
            print("(none set; `env.describe()` lists all %d honored knobs)"
                  % len(env.VARIABLES))
    except Exception as e:
        print("(registry unavailable: %s)" % (e,))
    for k in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        if k in os.environ:
            print("%-40s = %s" % (k, os.environ[k]))

    section("Native libraries")
    for rel in ("build/libmxtpu.so", "build/libmxnet_tpu_c.so"):
        path = os.path.join(REPO, rel)
        print("%-28s %s" % (rel, "built (%d bytes)" % os.path.getsize(path)
                            if os.path.exists(path) else "not built"))

    section("Devices")
    import jax
    devs = jax.devices()
    print("platform=%s devices=%d kind=%s"
          % (devs[0].platform, len(devs), devs[0].device_kind))
    from mxnet_tpu import util
    print("compile cache: %s" % util.compile_cache_dir())
    print("\ndiagnose done")


if __name__ == "__main__":
    main()
