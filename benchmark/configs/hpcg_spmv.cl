// The user's kernel: HPCG 3.1 src/ComputeSPMV_ref.cpp (one row loop over the row's stored nonzeros) with the matrix in CSR and the Sparse BLAS csrmv scalar alpha (beta = 0); one work-item a row.
__kernel void spmv(__global int* rowptr, __global int* col, __global float* val,
                   __global float* x, __global float* y, float alpha) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = rowptr[i]; j < rowptr[i + 1]; j++) { s += val[j] * x[col[j]]; }
    y[i] = alpha * s;
}
