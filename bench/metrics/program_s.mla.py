"""``program_s``'s reading in the cells of a configuration with latent attention
and experts (``mla-long-context``).  Moves setup_s."""
import harness

read = harness.load_reader("program_s")
