"""``crossbar_vmm_roofline.decode``'s reading in the cells of a configuration
with latent attention and experts (``mla-long-context``): the calls and
their shapes come from the cell's reference, every per-head ``W_uk``/
``W_uv`` call and every held expert's projections over its buffer among
them.  Moves itl_p95_ms."""
import harness

read = harness.load_reader("crossbar_vmm_roofline.decode")
