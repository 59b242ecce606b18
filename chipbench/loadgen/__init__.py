"""Load generation: a seeded request plan (``schedule``) and the child
process that sends it over the wire (``child``).  Nothing here imports JAX."""
