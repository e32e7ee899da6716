"""Plain ``jax.numpy`` references of the models in ``paddle_tpu.models``:
float32, no kernels, no chunking, no AMP.  What the Programs are tested
against on seeded weights (``tests/``)."""
