"""``python -m dlrover_tpu.native``: build every native library from
source and print the compiler's version and the library paths."""

import subprocess
import sys

from dlrover_tpu.native import build_library

LIBRARIES = ("fastcopy", "kv_store")


def main() -> int:
    version = subprocess.run(  # noqa: S603,S607
        ["g++", "--version"], capture_output=True, text=True,
        check=True,
    ).stdout.splitlines()[0]
    print(f"compiler: {version}")
    for name in LIBRARIES:
        print(f"built {name}: {build_library(name)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
