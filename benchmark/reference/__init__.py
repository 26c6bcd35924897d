"""The benchmark's own generators and plain references.

Nothing here imports the library under test.  ``poisson`` and
``kronecker`` make the inputs from a configuration and a seed;
``checks`` holds the comparisons that decide ``correct``; ``control``
holds the lower-precision stand-ins that those comparisons must fail.
"""
