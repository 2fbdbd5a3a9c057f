"""The benchmark's own tests: CPU, tiny sizes, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
