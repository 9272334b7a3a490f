"""Computing n-Gram Statistics in MapReduce -- jax/pallas reproduction.

Importing the package, or any of its subpackages, starts no JAX backend: a
process picks its platform (and, on the CPU, its device count) before the
first array is made.
"""
