"""``sample_ms``'s reading in the cells of a configuration with latent attention
and experts (``mla-long-context``).  Moves itl_p95_ms."""
import harness

read = harness.load_reader("sample_ms")
