"""One module per model family: its plain float32 reference and the work
its tokens need.

A configuration file names its module under ``"reference"``
(``spec.reference``), and the harness reaches the model only through it.
``references/<name>.py`` provides:

- ``dims_of(config)``: the sizes of the configuration file, in its
  source's keys with the values the program serves, as a frozen, hashable
  object.  It carries ``vocab``, ``n_layers``, and the work counts the
  readers take: ``decode_token_flops(ctx)``, ``prefill_flops(prompt_len)``
  and ``decode_attention_cost(ctxs)`` (one layer's flops and bytes).
- ``program_sizes(model_cfg)`` and ``sizes(dims)``: two dicts with the same
  keys, the program's sizes and the file's, which must agree.
- ``init_weights(dims, seed, dtype)``: the serving launcher's weight draw
  for this family, written out.
- ``forward(w, dims, tokens, lower) -> (logits, tie_margin)``: one
  sequence's logits at every position and its smallest router tie margin,
  which ``reference.served_gaps`` compares the served tokens with.

The counts are of the work a token needs, not of what the program
computes: the experts a token is routed to, not a padded expert capacity;
the positions a row holds, not the cache's width.  A roofline share above
100 % therefore means a count is wrong, or the time misses work.
"""
