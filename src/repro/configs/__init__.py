"""The paper's own CEC scenario config (``cec_paper``)."""
