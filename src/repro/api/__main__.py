import sys

from repro.api.cli import configure_compile_cache, main

if __name__ == "__main__":
    configure_compile_cache()
    sys.exit(main())
