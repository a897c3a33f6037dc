"""``decode_step_ms``'s reading in the cells of a configuration with latent attention
and experts (``mla-long-context``).  Moves itl_p95_ms."""
import harness

read = harness.load_reader("decode_step_ms")
