"""The benchmark's own parts: window and fences, traffic, trace reduction,
peaks, required work, plain references and the comparison that decides
``correct``.  Nothing here is imported by the program under test."""
