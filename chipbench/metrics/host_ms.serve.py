"""Host time per decode step: the median, over the window's
``serve.step`` spans, of each one's duration less its ``serve.wait``
(the host waiting for the step's tokens) and its ``serve.join`` children
(read by ``join_ms.serve``). What is left is emitting and retiring, the
step's dispatch and the tokens' copy to the host."""
import spans


def read(obs, cell, device):
    return spans.self_ms(obs, "serve.step", ("serve.wait", "serve.join"))
