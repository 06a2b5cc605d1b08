"""Plain references: a model's equations written out in ``jax.numpy``,
independent of the layers they check."""
