"""``decode_mfu``'s reading in the cells of a configuration with latent attention
and experts (``mla-long-context``): the model operations come from the
cell's reference, the absorbed attention over each row's context and each
held expert's share of the routed assignments among them.  Moves
itl_p95_ms."""
import harness

read = harness.load_reader("decode_mfu")
