"""One module per kind of answer, found by name: a closed-loop traffic
mix's ``answer``, or a key of an open-loop mix, which is also the kind a
``GraphService`` request asks for (``GraphService.submit(graph, kind)``).

Each has:

- ``answer(counter, csr)``: one answer through the program's
  ``TriangleCounter`` on the resident oriented graph, on the host;
- ``reference(edges, n_nodes)``: the plain reference's answer
  (``bench/reference.py``);
- ``compare(answers, ref) -> (failed, compared)``: how many of one
  graph's answers differ, and each number compared as
  ``{name: (value, limit)}``;
- ``control(edges, n_nodes, seed, counter_args)``: an answer that breaks
  the configuration's guarantee, which ``compare`` has to fail
  (``bench/control.py``).
"""
