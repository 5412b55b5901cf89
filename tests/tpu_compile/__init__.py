"""Compile-only checks for the TPU: libtpu is installed here, and a described
(not attached) v5e topology takes ``jit(...).lower(...).compile()``, which
raises what Mosaic would raise on the chip — a tile over VMEM, a slice off
the tiling. Nothing runs, so this says nothing about results or times.

One file a model family: under ``pytest-xdist --dist loadfile`` a file goes to
one worker, and a family's programs are one worker's serial work. Each worker
loads the TPU's library once; the topology is described inside a fixture
(``conftest.py``), never at import.
"""
