"""Pallas TPU kernels of the EDM hot path (DESIGN.md SS2/SS8): kNN-table
selection (knn_topk) and the batched CCM lookup (ccm_lookup).

Every wrapper takes ``interpret`` explicitly: False compiles with Mosaic
for the TPU, True runs the Pallas interpreter (tests and the
``pallas-interpret`` engine).  Nothing here picks a mode from the
backend it finds.
"""
